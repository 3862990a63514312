"""Bring-up smoke on the chip: the selector's served path and Qwen3-1.7B
serving, each driven once through the entry points a user calls.

    python chip_smoke.py             # one TPU: selector fleet, served path,
                                     # Qwen3-1.7B serving at full width
    python chip_smoke.py --chips 4   # four TPUs: the jax_sharded fleet only

Every phase prints one line: the seconds JAX spent tracing and compiling
for it, the rest of its wall time, the device's ``peak_bytes_in_use`` so
far, and what it compared.  The seconds are set-up facts of this run (a
warm or cold compile cache changes them), not measurements of the
system.  The last line of standard output is the device stamp as JSON.
Without a TPU the script exits non-zero before any phase runs; a failed
phase makes it exit non-zero and print no stamp.

Each phase is a function of its sizes, so a CPU test can call it at tiny
sizes (Pallas in interpret mode); only :func:`main` insists on the TPU
and the full sizes.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))
# the universe generators of the market benchmark and the served-path
# example, reused as they are
sys.path += [str(ROOT / "benchmarks"), str(ROOT / "examples")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import repro.configs as configs  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.market import (JournalReplayer, RecordedPriceFeed,  # noqa: E402
                          ServeFrontend, SimulatedSpotFeed, Submission,
                          record_feed)
from repro.models import build_model  # noqa: E402
from repro.models.types import ModelConfig  # noqa: E402
from repro.selector import (BatchedRankState, IdentityCatalog,  # noqa: E402
                            PallasBatchedRankState, PriceTable,
                            SelectionService, rank_dense, score_contract)
from repro.selector.sharded import ShardedBatchedRankState  # noqa: E402
from repro.serve.engine import Engine, Request, serving_params  # noqa: E402

from market_bench import _delta_batches, _fleet_members, _universe  # noqa: E402
from serve_frontend import build_universe  # noqa: E402

#: decode-vs-forward parity bound for bfloat16 serving, as the relative
#: L2 distance of a step's logits from the full forward's.  bfloat16
#: rounding alone gave ~0.05 (28 layers at reduced width, and the same
#: between a bfloat16 and a float32 forward); a decode that skipped its
#: cache write or used a shifted position gave 0.8-1.0.
LM_PARITY_REL_L2 = 0.15

#: JAX's own monitoring events for tracing, lowering and compiling
_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")


class SmokeFailure(AssertionError):
    """A phase's output disagreed with its reference."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


# --- the selector fleet: jax_batched and jax_pallas against numpy -----------

def _cold_reference(hours, mask, live, ids, members):
    """numpy float64 cold ranking per member: (ranking, score by id)."""
    out = {}
    for key, rows in members.items():
        ranking = rank_dense(hours[rows], mask[rows], live, ids)
        out[key] = (ranking, {r.config_id: r.score for r in ranking})
    return out


def _check_head(label, head, cold, contract, k) -> None:
    """A served top-k head against the cold ranking under ``contract``:
    an acceptable winner, and every entry's score both its config's cold
    score and the cold score at that rank (near-ties may swap)."""
    ranking, score_of = cold
    check(len(head) == k, f"{label}: head of {len(head)}, expected {k}")
    check(contract.winner_matches(head[0].config_id, ranking),
          f"{label}: winner {head[0]} against cold {ranking[0]}")
    for i, r in enumerate(head):
        check(contract.scores_match(r.score, score_of[r.config_id])
              and contract.scores_match(r.score, ranking[i].score),
              f"{label}: head[{i}] {r} against cold {ranking[i]} "
              f"(own cold score {score_of[r.config_id]})")


def _lowered_kernels(state: PallasBatchedRankState, k: int) -> bool:
    """Do both fused programs, lowered from the state's own tick dispatch
    (a no-change tick), hold a Mosaic kernel (``tpu_custom_call``) rather
    than the interpreter?"""
    unchanged = np.zeros_like(state._host_prices)
    texts = []
    for heads in (None, k):
        fn, args, tiles = state._tick_call(state._host_prices, unchanged,
                                           heads)
        texts.append(fn.lower(*args, **tiles).as_text())
    return all("tpu_custom_call" in t for t in texts)


def require_kernel(result: dict) -> dict:
    """The selector fleet's result, provided its Pallas programs were
    Mosaic kernels: on the chip nothing may fall back to the
    interpreter."""
    check(result["pallas_program"] == "tpu_custom_call",
          "jax_pallas lowered to the interpreter, not a Mosaic kernel")
    return result


def phase_selector_fleet(n_jobs: int = 64, n_cfgs: int = 10_000,
                         n_members: int = 8, n_ticks: int = 32,
                         frac: float = 0.01, k: int = 5, seed: int = 0
                         ) -> dict:
    """Reprice a fleet of live member selections through
    ``BatchedRankState`` (jax_batched) and ``PallasBatchedRankState``
    (jax_pallas: ``reprice`` + ``top_k`` on odd ticks,
    ``reprice_with_heads`` on even ones), and hold every member's head
    to a numpy cold ``rank_dense`` on every tick."""
    hours, mask, prices, ids, rng = _universe(n_jobs, n_cfgs, seed)
    batches = _delta_batches(ids, prices, rng, n_ticks, frac)
    members = _fleet_members(n_jobs, n_members, rng)
    batched = BatchedRankState(hours, mask, prices, ids)
    pallas = PallasBatchedRankState(hours, mask, prices, ids)
    for key, rows in members.items():
        batched.add_state(key, rows=rows)
        pallas.add_state(key, rows=rows)
    kernel = _lowered_kernels(pallas, k)
    pos = {c: i for i, c in enumerate(ids)}
    live = prices.copy()
    for t, batch in enumerate(batches):
        for cid, p in batch.items():
            live[pos[cid]] = p
        batched.reprice(batch)
        if t % 2:
            pallas.reprice(batch)
            p_heads = {key: pallas.top_k(key, k) for key in members}
        else:
            _, p_heads = pallas.reprice_with_heads(batch, k)
        cold = _cold_reference(hours, mask, live, ids, members)
        for key in members:
            _check_head(f"tick {t} jax_batched {key}",
                        batched.top_k(key, k), cold[key],
                        score_contract("jax_batched"), k)
            _check_head(f"tick {t} jax_pallas {key}", p_heads[key],
                        cold[key], score_contract("jax_pallas"), k)
    check(batched.dispatches == n_ticks and pallas.dispatches == n_ticks,
          f"dispatches {batched.dispatches}/{pallas.dispatches} for "
          f"{n_ticks} ticks")
    return {"universe": f"{n_jobs}x{n_cfgs}", "members": n_members,
            "ticks": n_ticks, "heads_within_contract": 2 * n_ticks * n_members,
            "pallas_program": "tpu_custom_call" if kernel else "interpret"}


# --- the served path: ServeFrontend on jax_batched, journal audit -----------

def phase_served_path(n_subs: int = 300, n_ticks: int = 40, workers: int = 2,
                      seed: int = 42) -> dict:
    """A threaded ``ServeFrontend`` on ``jax_batched`` (tick thread +
    ``workers`` snapshot workers) over a recorded ``SimulatedSpotFeed``,
    built as
    ``examples/serve_frontend.py`` builds it, closed cleanly, and its
    merged journal audited cold in tolerance mode."""
    store, ids, base = build_universe()
    feed = RecordedPriceFeed.loads(record_feed(
        SimulatedSpotFeed(base, seed=seed, change_fraction=0.5), n_ticks))
    service = SelectionService(IdentityCatalog(ids), store,
                               PriceTable(base), backend="jax_batched",
                               serve_top_k=3)
    selections = [("j1", None), ("j2", None), ("j3", None),
                  ("j4", None), ("j1", ("g2", "g3")), ("j2", ("g1",))]
    subs = [Submission(job, exclude_groups=excl)
            for job, excl in (selections[i % len(selections)]
                              for i in range(n_subs))]
    fe = ServeFrontend(service, feed, workers=workers,
                       queue_capacity=len(subs) + 1)
    fe.warm(subs[:len(selections)])
    with fe:
        for sub in subs:
            check(fe.submit(sub), "a submission was shed")
        fe.drain()
        fe.await_ticks()
    stats = fe.stats()
    audit = JournalReplayer(store, fe.journal_dump()).audit()
    check(stats.accounted and stats.decisions + stats.rejected == n_subs,
          f"accounting: {stats}")
    check(stats.ticks == n_ticks, f"{stats.ticks}/{n_ticks} ticks played")
    check(audit.contract == score_contract("jax_batched"),
          f"audited under {audit.contract}, served on jax_batched")
    check(audit.ok, f"journal audit mismatches: {audit.mismatches[:3]}")
    return {"backend": "jax_batched", "workers": workers,
            "decisions": stats.decisions, "rejected": stats.rejected,
            "ticks": stats.ticks, "audit": "clean",
            "drift_records": len(audit.drift),
            "reprice_dispatches": service.reprice_dispatches}


# --- LM serving: Engine over a full-width model in its compute dtype --------

def phase_lm_serving(cfg: ModelConfig, n_requests: int = 8, slots: int = 4,
                     prompt_len: int = 128, max_new: int = 16,
                     max_len: int = 256, seed: int = 0) -> dict:
    """Serve ``n_requests`` random prompts through ``Engine`` with the
    weights in the config's compute dtype, then replay the first wave
    through the engine's own prefill/decode steps: the replay must
    reproduce the served tokens, every logit must be finite, and each
    decode step's logits must match a full forward over the same tokens
    within :data:`LM_PARITY_REL_L2`."""
    model = build_model(cfg)
    params = serving_params(model, seed)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    eng = Engine(model, params, slots=slots, max_len=max_len)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), n_requests)
    reqs = [Request(uid=i, max_new_tokens=max_new,
                    prompt=jax.random.randint(keys[i], (prompt_len,), 0,
                                              cfg.vocab_size, jnp.int32))
            for i in range(n_requests)]
    comps = {c.uid: c for c in eng.serve(reqs)}
    check(sorted(comps) == list(range(n_requests))
          and all(len(c.tokens) == max_new for c in comps.values()),
          f"completions: {[(u, len(c.tokens)) for u, c in comps.items()]}")

    wave = reqs[:slots]
    prompts = jnp.stack([r.prompt for r in wave])
    served = np.asarray([comps[r.uid].tokens for r in wave], np.int32)
    state = eng._init_state()
    logits, state = eng._prefill(params, {"tokens": prompts}, state)
    steps = [logits]
    for t in range(max_new - 1):
        logits, state = eng._decode(params, jnp.asarray(served[:, t]),
                                    jnp.int32(prompt_len + t), state)
        steps.append(logits)
    dec = jnp.stack(steps, axis=1).astype(jnp.float32)   # (slots, new, V)
    tokens = jnp.concatenate([prompts, jnp.asarray(served[:, :-1])], axis=1)
    full, _ = jax.jit(lambda p, b: model.forward(p, b, remat=False))(
        params, {"tokens": tokens})
    ref = full[:, prompt_len - 1:].astype(jnp.float32)
    check(bool(jnp.isfinite(dec).all() & jnp.isfinite(ref).all()),
          "non-finite logits")
    check(np.array_equal(np.asarray(jnp.argmax(dec, axis=-1)), served),
          "replaying the engine's steps did not reproduce its tokens")
    rel = np.asarray(jnp.linalg.norm(dec - ref, axis=-1)
                     / jnp.linalg.norm(ref, axis=-1))
    check(float(rel.max()) <= LM_PARITY_REL_L2,
          f"decode/forward relative L2 {float(rel.max()):.4f} > "
          f"{LM_PARITY_REL_L2}")
    return {"model": cfg.name, "params": int(n_params),
            "dtype": str(jnp.dtype(cfg.compute_dtype)),
            "requests": n_requests, "slots": slots,
            "prompt_len": prompt_len, "new_tokens": max_new,
            "decode_vs_forward_rel_l2_max": float(rel.max()),
            "bound": LM_PARITY_REL_L2}


# --- four chips: the jax_sharded fleet against jax_batched and numpy --------

def phase_sharded_fleet(n_devices: int = 4, n_jobs: int = 64,
                        n_cfgs: int = 10_240, n_members: int = 8,
                        n_ticks: int = 32, frac: float = 0.01, k: int = 5,
                        seed: int = 0) -> dict:
    """``ShardedBatchedRankState`` over ``n_devices`` devices, compared
    on every tick with ``BatchedRankState`` on device 0 and with numpy
    cold, under the jax contract, at one collective dispatch per tick."""
    hours, mask, prices, ids, rng = _universe(n_jobs, n_cfgs, seed)
    batches = _delta_batches(ids, prices, rng, n_ticks, frac)
    members = _fleet_members(n_jobs, n_members, rng)
    sharded = ShardedBatchedRankState(hours, mask, prices, ids,
                                      devices=n_devices)
    batched = BatchedRankState(hours, mask, prices, ids)
    for key, rows in members.items():
        sharded.add_state(key, rows=rows)
        batched.add_state(key, rows=rows)
    placed = {s.device for s in sharded.d_scores.addressable_shards}
    check(placed == set(jax.devices()[:n_devices]),
          f"score shards on {sorted(d.id for d in placed)}")
    contract = score_contract("jax_sharded")
    pos = {c: i for i, c in enumerate(ids)}
    live = prices.copy()
    for t, batch in enumerate(batches):
        for cid, p in batch.items():
            live[pos[cid]] = p
        sharded.reprice(batch)
        batched.reprice(batch)
        cold = _cold_reference(hours, mask, live, ids, members)
        for key in members:
            s_head = sharded.top_k(key, k)
            b_head = batched.top_k(key, k)
            _check_head(f"tick {t} jax_sharded {key}", s_head, cold[key],
                        contract, k)
            _check_head(f"tick {t} jax_batched {key}", b_head, cold[key],
                        score_contract("jax_batched"), k)
            check(all(contract.scores_match(s.score, b.score)
                      for s, b in zip(s_head, b_head)),
                  f"tick {t} {key}: sharded {s_head} vs batched {b_head}")
    check(sharded.dispatches == n_ticks,
          f"{sharded.dispatches} collective dispatches for {n_ticks} ticks")
    return {"devices": n_devices, "universe": f"{n_jobs}x{n_cfgs}",
            "members": n_members, "ticks": n_ticks,
            "collective_dispatches_per_tick": sharded.dispatches / n_ticks,
            "heads_within_contract": n_ticks * n_members}


# --- entry point --------------------------------------------------------------

class _CompileClock:
    """Seconds JAX reports spending on tracing, lowering and compiling."""

    def __init__(self):
        self.seconds = 0.0

    def __call__(self, event: str, duration: float, **_) -> None:
        if event in _COMPILE_EVENTS:
            self.seconds += duration


def _run_phase(name, fn, clock: _CompileClock, devices) -> bool:
    c0, t0 = clock.seconds, time.perf_counter()
    try:
        result = fn()
    except Exception:
        traceback.print_exc()
        print(f"[{name}] FAIL", flush=True)
        return False
    wall = time.perf_counter() - t0
    compile_s = clock.seconds - c0
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    print(f"[{name}] PASS set-up facts, not measurements: "
          f"compile_s={compile_s:.1f} run_s={wall - compile_s:.1f} "
          f"peak_bytes_in_use={peak} | {json.dumps(result)}", flush=True)
    return True


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the jax_sharded fleet across four "
                         "chips")
    args = ap.parse_args(argv)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < args.chips:
        print(f"chip_smoke: needs {args.chips} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform} device(s). "
              f"Nothing ran.", file=sys.stderr)
        return 1
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    clock = _CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock)
    used = devices[:args.chips]
    if args.chips == 4:
        phases = [("sharded_fleet", phase_sharded_fleet)]
    else:
        phases = [("selector_fleet",
                   lambda: require_kernel(phase_selector_fleet())),
                  ("served_path", phase_served_path),
                  ("lm_serving",
                   lambda: phase_lm_serving(configs.get("qwen3-1.7b")))]
    results = [_run_phase(name, fn, clock, used) for name, fn in phases]
    if not all(results):
        return 1
    dev = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
