"""Batched serving engine: prefill + decode with continuous slot reuse.

The engine keeps a fixed decode batch of ``slots``; finished sequences free
their slot, which the admission loop refills from the request queue
(continuous batching at slot granularity).  All sequences in a decode batch
share the position counter — a slot admitted mid-stream left-pads so its
cache lines up (the standard static-batching trade-off; per-slot position
tensors are a documented extension).

``serve_step`` — one token for the whole batch against the KV/recurrent
state — is the unit the dry-run lowers for the ``decode_*`` cells.

Fleet placement: :func:`plan_decode_placement` asks a
:class:`repro.selector.SelectionService` which profiled mesh the decode
fleet should run on under current chip prices (DESIGN.md §3); the
resulting :class:`repro.selector.Decision` can be attached to the engine
as ``placement`` so serving metadata records where (and at what $/h) the
batch is meant to run.
"""
from __future__ import annotations

import dataclasses
import queue
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.types import ModelConfig
from repro.obs import MetricsRegistry
from repro.selector import Decision, SelectionService


def plan_decode_placement(service: SelectionService,
                          shape_name: str = "decode_32k",
                          *, annotation=None,
                          exclude_archs: Tuple[str, ...] = (),
                          current: Optional[Decision] = None,
                          switch_cost_hours: float = 0.25,
                          horizon_hours: float = 24.0,
                          hysteresis: float = 1.25) -> Decision:
    """Pick the mesh for a decode fleet via the selection service.

    ``shape_name`` is the workload cell the fleet serves (class A,
    state-resident, unless annotated otherwise); the service ranks every
    profiled mesh option by summed normalized cost under current prices.

    With ``current`` (the fleet's standing placement decision), the
    hysteresis advisor (:func:`repro.market.should_migrate`, DESIGN.md
    §6) gates the move: a running fleet only switches mesh when projected
    savings over ``horizon_hours`` beat ``hysteresis`` times the
    ``switch_cost_hours`` of dual-running during cutover.  When the
    advisor says stay, the returned Decision keeps the current mesh but
    is re-stamped with today's ranking, $/h and price epoch.
    """
    decision = service.submit(shape_name, annotation=annotation,
                              exclude_groups=exclude_archs)
    if current is None or decision.config_id == current.config_id:
        return decision
    from repro.market.migration import should_migrate
    try:
        # quote savings/switch cost off today's rate, not the $/h stamped
        # when `current` was decided (which may predate any price move)
        current_rate: Optional[float] = service.catalog.hourly_cost(
            current.config_id, service.price_source)
    except KeyError:
        # deprovisioned entry: the advisor sees it as unrankable and
        # forces the move off the stamped rate
        current_rate = None
    advice = should_migrate(current, decision.ranking, switch_cost_hours,
                            horizon_hours=horizon_hours,
                            hysteresis=hysteresis,
                            current_hourly_cost=current_rate)
    if advice.migrate:
        return decision
    return dataclasses.replace(
        decision, config_id=current.config_id,
        entry=service.catalog.entry(current.config_id),
        hourly_cost=current_rate)


def serving_params(model, seed: int = 0):
    """The model's weights for serving, made from ``seed``: initialised
    on the device in one jitted call and cast to the config's compute
    dtype, so a full-width model never holds float32 weights at once."""
    dtype = model.cfg.compute_dtype
    return jax.jit(lambda key: jax.tree.map(
        lambda x: x.astype(dtype), model.init(key)))(jax.random.PRNGKey(seed))


@dataclasses.dataclass
class Request:
    uid: int
    prompt: jax.Array              # (T,) int32
    max_new_tokens: int = 32
    eos_id: Optional[int] = None


@dataclasses.dataclass
class Completion:
    uid: int
    tokens: List[int]
    prefill_ms: float
    decode_ms: float


class Engine:
    """Greedy-decoding engine over a fixed slot batch."""

    def __init__(self, model, params, *, slots: int, max_len: int,
                 enc_len: int = 0, placement: Optional[Decision] = None,
                 metrics: Optional[MetricsRegistry] = None):
        self.model = model
        self.cfg: ModelConfig = model.cfg
        self.params = params
        self.slots = slots
        self.max_len = max_len
        self.enc_len = enc_len
        #: where this fleet is meant to run (selector decision), if planned.
        self.placement = placement
        #: telemetry (DESIGN.md §12): per-wave ``serve.prefill`` /
        #: ``serve.decode`` histograms next to the Completion ms fields,
        #: timed off the registry's injectable clock.
        self.metrics = metrics
        self._clock = metrics.clock if metrics is not None \
            else time.perf_counter
        self._h_prefill = metrics.histogram("serve.prefill") \
            if metrics is not None else None
        self._h_decode = metrics.histogram("serve.decode") \
            if metrics is not None else None

        self._prefill = jax.jit(
            lambda p, b, s: model.prefill(p, b, s))
        self._decode = jax.jit(
            lambda p, t, pos, s: model.decode_step(p, t, pos, s))

    def _init_state(self):
        if self.cfg.is_encdec:
            return self.model.init_state(self.slots, self.max_len,
                                         self.enc_len)
        return self.model.init_state(self.slots, self.max_len)

    def generate_batch(self, requests: List[Request]) -> List[Completion]:
        """Serve a wave of requests of equal prompt length (greedy)."""
        assert 0 < len(requests) <= self.slots
        reqs = list(requests)
        while len(reqs) < self.slots:       # pad with a copy; discarded later
            reqs.append(dataclasses.replace(reqs[-1], uid=-1))
        prompts = jnp.stack([r.prompt for r in reqs])
        t0 = self._clock()
        state = self._init_state()
        batch = {"tokens": prompts}
        logits, state = self._prefill(self.params, batch, state)
        jax.block_until_ready(logits)
        t1 = self._clock()
        if self._h_prefill is not None:
            self._h_prefill.observe(t1 - t0)

        T_p = prompts.shape[1]
        max_new = max(r.max_new_tokens for r in reqs)
        out_tokens = [[] for _ in reqs]
        done = [False] * len(reqs)
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        for step in range(max_new):
            for i, r in enumerate(reqs):
                t = int(tok[i])
                if not done[i]:
                    out_tokens[i].append(t)
                    if (r.eos_id is not None and t == r.eos_id) or \
                            len(out_tokens[i]) >= r.max_new_tokens:
                        done[i] = True
            if all(done):
                break
            pos = jnp.int32(T_p + step)
            if int(pos) >= self.max_len:
                break
            logits, state = self._decode(self.params, tok, pos, state)
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        t2 = self._clock()
        if self._h_decode is not None:
            self._h_decode.observe(t2 - t1)
        return [Completion(uid=r.uid, tokens=out_tokens[i],
                           prefill_ms=(t1 - t0) * 1e3,
                           decode_ms=(t2 - t1) * 1e3)
                for i, r in enumerate(reqs) if r.uid >= 0]

    def serve(self, requests: List[Request]) -> List[Completion]:
        """Continuous admission: waves of up to ``slots`` requests."""
        out: List[Completion] = []
        pending = queue.SimpleQueue()
        for r in requests:
            pending.put(r)
        while not pending.empty():
            wave = []
            while len(wave) < self.slots and not pending.empty():
                wave.append(pending.get())
            out.extend(self.generate_batch(wave))
        return out


def make_serve_step(model) -> Callable:
    """The unit the dry-run lowers for decode cells."""
    def serve_step(params, token, pos, state):
        return model.decode_step(params, token, pos, state)
    return serve_step
