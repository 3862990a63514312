"""Multi-device sharded fleet repricing (the ``"jax_sharded"`` backend).

:class:`ShardedBatchedRankState` is :class:`~repro.selector.rank.
BatchedRankState` with the config (C) axis sharded across a 1-D device
mesh via ``jax.shard_map`` (DESIGN.md §13).  Catalogs of
100k+ configs (multi-region × multi-cloud × spot/on-demand) no longer
need to fit one device: every C-extent buffer — hours, mask, cost,
normalized cost, prices, and the S×C member score accumulators — lives
in contiguous per-device column blocks, and a price tick is ONE
collective dispatch in which each shard replays the familiar delta
step on its own columns, with exactly two cross-device collectives:

* ``lax.psum`` of the per-shard "my row minimum may have moved" flags
  (handoff detection must see every shard's columns), and
* ``lax.pmin`` of the per-shard masked row minima (the global row-min
  that every shard's normalization divides by).

Both collectives combine *exact* values (booleans; an elementwise
float min), so the arithmetic per cell is the same float32 expression
as the single-device batched kernel and the ``jax_batched``
ScoreContract envelope carries over unchanged.

**Serving** keeps the catalog-order tie-break exact without gathering
the score row: each shard runs ``lax.top_k`` over its local columns
(which breaks score ties by lower *local* index), local indices are
lifted to global catalog positions (``shard offset + local index`` —
monotone within a shard, so the within-shard order is already the
global ``(score, catalog position)`` order), and the host merges the
``devices × k_local`` candidates by ``(score, global index)``.  The
merged head is element-wise identical to ``ranking()[:k]``, ties
included, so journals audit unchanged.

**Delta routing**: a tick's changed columns are routed to their owning
shard on the host (owner = column // shard width) and padded to a
power-of-4 bucket *per shard*, so the collective step compiles
O(log C) shape variants exactly like the single-device states.  Shards
with no changed column this tick receive an idempotent no-op pair
(their local column 0 re-set to its current price).

Like the rest of the jax family, importing this module never
initializes a backend; kernels compile on first use, per device count.
"""
from __future__ import annotations

import threading
from typing import (Any, Dict, Hashable, List, Mapping, Optional,
                    Sequence, Tuple, Union)

import numpy as np

from repro.obs import MetricsRegistry, maybe_span

from .rank import (SCORE_CONTRACTS, BackendUnavailableError,
                   NothingRankableError, RankedConfig,
                   _bucket_size, _canonicalize_universe, _check_k,
                   _materialize, _position_index, _validated_deltas,
                   _HAVE_JAX)

if _HAVE_JAX:
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from .rank import _fleet_matmul

#: span names the sharded tick emits when a MetricsRegistry is wired in
STEP_SPAN = "shard.step"
MERGE_SPAN = "shard.merge"

# jitted-kernel caches, keyed per device count (the mesh is part of the
# shard_map closure).  k in the top-k kernel is additionally static,
# like the single-device top_k — one compile per (device count, depth).
# Builds run under the lock (double-checked, like the rank.py
# singletons): concurrent first-calls from the serving front-end's
# workers would otherwise build the same mesh kernels twice.
_FNS: "Dict[int, Tuple[Any, Any, Any]]" = {}
_TOPK: "Dict[Tuple[int, int, int], Any]" = {}
_SHARDED_LOCK = threading.Lock()


def _mesh(n_dev: int) -> "Mesh":
    return Mesh(np.asarray(jax.devices()[:n_dev]), ("c",))


def _sharded_fns(n_dev: int) -> Tuple[Any, Any, Any]:
    """``(cold, step, member_scores)`` jitted collective kernels for an
    ``n_dev``-device mesh, built once per device count.

    Per-shard shapes: every C-extent axis holds ``C_pad / n_dev``
    columns; the member axis (S), job axis (J) and row-min vector are
    replicated.  The step is the batched delta step with the row-min
    handoff test and the fresh row minima lifted to collectives — see
    the module docstring for why that preserves the single-device
    arithmetic per cell.
    """
    cached = _FNS.get(n_dev)
    if cached is not None:
        return cached
    with _SHARDED_LOCK:
        cached = _FNS.get(n_dev)
        if cached is not None:
            return cached
        return _build_sharded_fns(n_dev)


def _build_sharded_fns(n_dev: int) -> Tuple[Any, Any, Any]:
    mesh = _mesh(n_dev)
    spec_c = P(None, "c")   # (rows, C_pad) matrices, C sharded
    spec_v = P("c")         # (C_pad,) vectors
    spec_r = P()            # replicated

    def cold_local(hours, mask, prices):
        cost = jnp.where(mask, hours * prices[None, :], jnp.inf)
        row_best = jax.lax.pmin(cost.min(axis=1), "c")
        norm = jnp.where(mask, cost / row_best[:, None], 0.0)
        return cost, row_best, norm

    def step_local(prices, cost, row_best, norm, scores, hours, mask,
                   row_masks, cols, new_prices):
        # the routed delta arrays arrive stacked (n_dev, bucket); each
        # shard sees its own (1, bucket) slice
        cols = cols[0]
        new_prices = new_prices[0]
        # -- local half: identical to _delta_universe_update on this
        #    shard's columns
        sub_mask = mask[:, cols]
        new_cost = jnp.where(sub_mask,
                             hours[:, cols] * new_prices[None, :],
                             jnp.inf)
        old_cost = cost[:, cols]
        prices = prices.at[cols].set(new_prices)
        cost = cost.at[:, cols].set(new_cost)
        was_min = old_cost.min(axis=1) == row_best
        undercut = new_cost.min(axis=1) < row_best
        # -- collective half: a row's minimum may live on any shard, so
        #    the handoff test and the fresh minima are fleet-wide
        need = jax.lax.psum((was_min | undercut).astype(jnp.int32),
                            "c") > 0
        gmin = jax.lax.pmin(cost.min(axis=1), "c")
        fresh = jnp.where(need, gmin, row_best)
        moved = fresh != row_best
        row_best = fresh
        # -- consumer half: same two matmuls as the batched kernel,
        #    each shard refreshing its own score columns
        fresh_rows = jnp.where(mask, cost / row_best[:, None], 0.0)
        col_norm = jnp.where(sub_mask,
                             cost[:, cols] / row_best[:, None], 0.0)
        row_delta = jnp.where(moved[:, None], fresh_rows - norm, 0.0)
        scores = scores + _fleet_matmul(row_masks, row_delta)
        norm = jnp.where(moved[:, None], fresh_rows, norm)
        norm = norm.at[:, cols].set(col_norm)
        scores = scores.at[:, cols].set(_fleet_matmul(row_masks, col_norm))
        return prices, cost, row_best, norm, scores, moved.sum()

    def member_local(norm, row_mask):
        # a new member's accumulators from the current shared norm
        return _fleet_matmul(row_mask, norm)

    donate = () if jax.default_backend() == "cpu" else (0, 1, 2, 3, 4)
    cold = jax.jit(jax.shard_map(
        cold_local, mesh=mesh,
        in_specs=(spec_c, spec_c, spec_v),
        out_specs=(spec_c, spec_r, spec_c),
        check_vma=False))
    step = jax.jit(jax.shard_map(
        step_local, mesh=mesh,
        in_specs=(spec_v, spec_c, spec_r, spec_c, spec_c, spec_c,
                  spec_c, spec_r, P("c", None), P("c", None)),
        out_specs=(spec_v, spec_c, spec_r, spec_c, spec_c, spec_r),
        check_vma=False), donate_argnums=donate)
    member = jax.jit(jax.shard_map(
        member_local, mesh=mesh,
        in_specs=(spec_c, spec_r),
        out_specs=spec_v,
        check_vma=False))
    _FNS[n_dev] = (cold, step, member)
    return _FNS[n_dev]


def _sharded_topk_fn(n_dev: int, k_loc: int, c_loc: int) -> Any:
    """Per-shard head extraction: each shard top-k's its own columns of
    one member's score row and lifts local indices to global catalog
    positions.  The member slot is a *traced* scalar, so serving a
    different member never recompiles; ``k_loc`` is static like every
    other top-k depth.  Returns the stacked ``(n_dev * k_loc,)``
    candidate ``(global index, score)`` arrays the host merge sorts.
    The shard width ``c_loc`` is baked into the index lift, so it is
    part of the cache key — states over different catalogs sharing a
    device count and depth must not share a kernel."""
    key = (n_dev, k_loc, c_loc)
    cached = _TOPK.get(key)
    if cached is not None:
        return cached
    with _SHARDED_LOCK:
        cached = _TOPK.get(key)
        if cached is not None:
            return cached
        return _build_sharded_topk_fn(key)


def _build_sharded_topk_fn(key: Tuple[int, int, int]) -> Any:
    n_dev, k_loc, c_loc = key
    mesh = _mesh(n_dev)

    def topk_local(scores, finite, slot):
        row = scores[slot]
        masked = jnp.where(finite[slot], row, jnp.inf)
        # ascending rank via negation; lax.top_k breaks ties by lower
        # local index == lower global index within the shard block
        neg, idx = jax.lax.top_k(-masked, k_loc)
        gidx = jax.lax.axis_index("c") * c_loc + idx
        return gidx, -neg

    fn = jax.jit(jax.shard_map(
        topk_local, mesh=mesh,
        in_specs=(P(None, "c"), P(None, "c"), P()),
        out_specs=(P("c"), P("c")),
        check_vma=False))
    _TOPK[key] = fn
    return fn


class ShardedBatchedRankState:
    """A :class:`~repro.selector.rank.BatchedRankState` whose config
    axis is sharded across a 1-D device mesh — one *collective* kernel
    dispatch per tick refreshes every member ranking at catalogs no
    single device holds (DESIGN.md §13).

    The member API is the batched state's: :meth:`add_state` /
    :meth:`retire_state` over slot tables with doubling capacity and
    slot reuse, :meth:`reprice` applying one delta batch fleet-wide,
    :meth:`ranking` / :meth:`top_k` / :meth:`winner` serving per
    member.  ``dispatches`` counts collective dispatches (one per
    tick); ``realloc_count`` counts capacity doublings.

    ``devices`` selects how many local devices to shard over (default:
    all).  ``C`` is padded up to a multiple of the device count with
    unprofiled, never-winning pad columns; all padding is invisible at
    the API surface.

    **Contract** (:data:`SCORE_CONTRACTS` ``["jax_sharded"]``): the
    ``jax_batched`` float32 envelope — the collectives combine exact
    values, so sharding relocates arithmetic without changing it.
    """

    backend = "jax_sharded"
    contract = SCORE_CONTRACTS["jax_sharded"]
    _BUCKET_BASE = 8
    _CAPACITY_BASE = 8

    def __init__(self, hours: np.ndarray, mask: np.ndarray,
                 prices: np.ndarray, config_ids: Sequence[Hashable],
                 job_ids: Optional[Sequence[Hashable]] = None,
                 capacity: Optional[int] = None,
                 devices: Optional[int] = None,
                 metrics: Optional[MetricsRegistry] = None):
        if not _HAVE_JAX:
            raise BackendUnavailableError(
                "ShardedBatchedRankState requires jax; use RankState "
                "(numpy) when it is not installed")
        avail = jax.device_count()
        n_dev = avail if devices is None else int(devices)
        if not 1 <= n_dev <= avail:
            raise ValueError(f"devices={devices!r} not in [1, {avail}] "
                             f"(local device count)")
        self.n_devices = n_dev
        self.config_ids = list(config_ids)
        self.job_ids = list(job_ids) if job_ids is not None else None
        self._metrics = metrics
        self._c_mat = (None if metrics is None
                       else metrics.counter("rank.materializations"))
        hours, mask, prices = _canonicalize_universe(hours, mask, prices,
                                                     self.job_ids)
        self._pos = _position_index(self.config_ids)
        self._job_pos = (None if self.job_ids is None else
                         {j: i for i, j in enumerate(self.job_ids)})
        self._mask = mask                     # host copy: member counts
        self._n_jobs = hours.shape[0]
        n_cfgs = len(self.config_ids)
        # contiguous block layout: shard d owns global columns
        # [d*C_loc, (d+1)*C_loc); the last block may be pure padding
        # tail (mask False -> cost +inf -> never wins, filtered from
        # every head by global index >= C)
        self._c_loc = -(-n_cfgs // n_dev)
        self._c_pad = self._c_loc * n_dev
        pad = self._c_pad - n_cfgs

        self._cold, self._step, self._member_scores = _sharded_fns(n_dev)
        self._mesh_obj = _mesh(n_dev)
        self._spec_c = NamedSharding(self._mesh_obj, P(None, "c"))
        self._spec_v = NamedSharding(self._mesh_obj, P("c"))
        self._spec_r = NamedSharding(self._mesh_obj, P())
        self._spec_d = NamedSharding(self._mesh_obj, P("c", None))

        def padded(x, fill):
            if pad == 0:
                return x
            width = [(0, 0)] * (x.ndim - 1) + [(0, pad)]
            return np.pad(x, width, constant_values=fill)

        hours32 = padded(hours.astype(np.float32), 1.0)
        mask_p = padded(mask, False)
        prices32 = padded(prices.astype(np.float32), 1.0)
        # host float32 mirror of the device price vector: the source of
        # the idempotent no-op pair routed to shards with no delta this
        # tick (must be the *kernel's* float32 quote, so the re-set is
        # an exact no-op on device)
        self._price_mirror = prices32.copy()

        self.d_hours = jax.device_put(hours32, self._spec_c)
        self.d_mask = jax.device_put(mask_p, self._spec_c)
        self.d_prices = jax.device_put(prices32, self._spec_v)
        self.d_cost, self.d_row_best, self.d_norm = self._cold(
            self.d_hours, self.d_mask, self.d_prices)

        cap = self._CAPACITY_BASE if capacity is None else max(1, capacity)
        self._capacity = cap
        self._slots: "dict[Hashable, int]" = {}
        self._retired: "set" = set()
        self._free: List[int] = list(range(cap - 1, -1, -1))
        self.d_row_masks = jax.device_put(
            np.zeros((cap, self._n_jobs), np.float32), self._spec_r)
        self.d_scores = jax.device_put(
            np.zeros((cap, self._c_pad), np.float32), self._spec_c)
        self._counts = np.zeros((cap, n_cfgs), dtype=np.int64)
        self._d_finite = jax.device_put(
            np.zeros((cap, self._c_pad), bool), self._spec_c)
        self.reprices = 0
        #: collective dispatches; one tick == one collective dispatch
        #: regardless of member or device count (the benchmark's
        #: ``one_dispatch_per_tick`` gate reads this).
        self.dispatches = 0
        self.realloc_count = 0
        self.materializations = 0
        self._ranking_memo: "dict[Hashable, Tuple[int, List[RankedConfig]]]" = {}

    # -- member management (same surface as BatchedRankState) ---------------
    def __contains__(self, key: Hashable) -> bool:
        return key in self._slots

    @property
    def n_active(self) -> int:
        """Live member count (what one collective dispatch refreshes)."""
        return len(self._slots)

    def keys(self) -> List[Hashable]:
        return list(self._slots)

    def _slot_of(self, key: Hashable) -> int:
        try:
            return self._slots[key]
        except KeyError:
            if key in self._retired:
                raise NothingRankableError(
                    f"member state {key!r} was retired")
            raise ValueError(f"unknown member state {key!r}")

    def _grow(self) -> None:
        cap = self._capacity * 2
        row_masks = np.zeros((cap, self._n_jobs), np.float32)
        row_masks[:self._capacity] = np.asarray(self.d_row_masks)
        scores = np.zeros((cap, self._c_pad), np.float32)
        scores[:self._capacity] = np.asarray(self.d_scores)
        finite = np.zeros((cap, self._c_pad), bool)
        finite[:self._capacity] = np.asarray(self._d_finite)
        self.d_row_masks = jax.device_put(row_masks, self._spec_r)
        self.d_scores = jax.device_put(scores, self._spec_c)
        self._d_finite = jax.device_put(finite, self._spec_c)
        counts = np.zeros((cap, len(self.config_ids)), dtype=np.int64)
        counts[:self._capacity] = self._counts
        self._counts = counts
        self._free.extend(range(cap - 1, self._capacity - 1, -1))
        self._capacity = cap
        self.realloc_count += 1

    def _rows_of(self, rows: Optional[Sequence[int]],
                 jobs: Optional[Sequence[Hashable]]) -> np.ndarray:
        if (rows is None) == (jobs is None):
            raise ValueError("pass exactly one of rows= or jobs=")
        if jobs is not None:
            if self._job_pos is None:
                raise ValueError(
                    "jobs= needs a state constructed with job_ids")
            try:
                rows = [self._job_pos[j] for j in jobs]
            except KeyError as e:
                raise ValueError(f"unknown job id {e.args[0]!r}")
        idx = np.asarray(list(rows), dtype=np.intp)
        if idx.size and (idx.min() < 0 or idx.max() >= self._n_jobs):
            raise ValueError(f"row index out of range for "
                             f"{self._n_jobs} jobs")
        if np.unique(idx).size != idx.size:
            raise ValueError("duplicate rows in member selection")
        return idx

    def add_state(self, key: Hashable, *,
                  rows: Optional[Sequence[int]] = None,
                  jobs: Optional[Sequence[Hashable]] = None) -> None:
        """Register a member ranking over a subset of the job axis; its
        accumulators come from the *current* shared (sharded) norm, so
        a member added mid-stream is in sync with every tick so far.
        Retired slots are reused before capacity grows."""
        if key in self._slots:
            raise ValueError(f"duplicate member state {key!r}")
        self._retired.discard(key)      # re-registering revives the key
        idx = self._rows_of(rows, jobs)
        if not self._free:
            self._grow()
        slot = self._free.pop()
        row_mask = np.zeros(self._n_jobs, dtype=np.float32)
        row_mask[idx] = 1.0
        counts = self._mask[idx].sum(axis=0) if idx.size else \
            np.zeros(len(self.config_ids), dtype=np.int64)
        d_row = jax.device_put(row_mask, self._spec_r)
        member_row = self._member_scores(self.d_norm, d_row)
        self.d_row_masks = jax.device_put(
            self.d_row_masks.at[slot].set(d_row), self._spec_r)
        self.d_scores = jax.device_put(
            self.d_scores.at[slot].set(member_row), self._spec_c)
        self._counts[slot] = counts
        finite = np.zeros(self._c_pad, bool)
        finite[:len(self.config_ids)] = counts > 0
        self._d_finite = jax.device_put(
            self._d_finite.at[slot].set(jax.device_put(
                finite, self._spec_v)), self._spec_c)
        self._slots[key] = slot

    def retire_state(self, key: Hashable) -> None:
        """Drop a member: its slot is zero-masked and reused by the
        next :meth:`add_state`; serving it afterwards raises
        :class:`NothingRankableError` (same semantics as the
        single-device batched state)."""
        slot = self._slots.pop(key, None)
        if slot is None:
            raise ValueError(f"unknown member state {key!r}")
        self.d_row_masks = jax.device_put(
            self.d_row_masks.at[slot].set(
                jnp.zeros(self._n_jobs, jnp.float32)), self._spec_r)
        self.d_scores = jax.device_put(
            self.d_scores.at[slot].set(jax.device_put(
                np.zeros(self._c_pad, np.float32), self._spec_v)),
            self._spec_c)
        self._counts[slot] = 0
        self._d_finite = jax.device_put(
            self._d_finite.at[slot].set(jax.device_put(
                np.zeros(self._c_pad, bool), self._spec_v)),
            self._spec_c)
        self._ranking_memo.pop(key, None)
        self._retired.add(key)
        self._free.append(slot)

    # -- the collective tick ------------------------------------------------
    @property
    def prices(self) -> np.ndarray:
        """Current per-config $/h as seen by the kernel (float32 quotes
        lifted to a host float64 vector; padding dropped)."""
        return np.asarray(self.d_prices,
                          dtype=np.float64)[:len(self.config_ids)]

    def scores(self, key: Hashable) -> np.ndarray:
        """A member's score accumulators on the host (float64 lift;
        padding dropped)."""
        return np.asarray(self.d_scores[self._slot_of(key)],
                          dtype=np.float64)[:len(self.config_ids)]

    def counts(self, key: Hashable) -> np.ndarray:
        """A member's per-config contributing-cell counts."""
        return self._counts[self._slot_of(key)].copy()

    def _route_deltas(self, cols: np.ndarray, new_prices: np.ndarray
                      ) -> Tuple[np.ndarray, np.ndarray]:
        """Host-side shard routing: owner = column // shard width,
        local index = column % shard width; each shard's batch is
        padded to the shared power-of-4 bucket by repeating its first
        (column, price) pair (idempotent under the kernel's ``.set``).
        A shard with no delta this tick gets its local column 0 re-set
        to the current float32 quote — an exact device no-op."""
        n_dev, c_loc = self.n_devices, self._c_loc
        owner = cols // c_loc
        local = (cols % c_loc).astype(np.int32)
        per = [np.flatnonzero(owner == d) for d in range(n_dev)]
        bucket = _bucket_size(max(1, max(len(p) for p in per)),
                              self._BUCKET_BASE)
        cols_sh = np.zeros((n_dev, bucket), np.int32)
        newp_sh = np.empty((n_dev, bucket), np.float32)
        for d, idx in enumerate(per):
            if len(idx):
                n = len(idx)
                cols_sh[d, :n] = local[idx]
                newp_sh[d, :n] = new_prices[idx]
                cols_sh[d, n:] = local[idx[0]]
                newp_sh[d, n:] = new_prices[idx[0]]
            else:
                newp_sh[d, :] = self._price_mirror[d * c_loc]
        # keep the mirror current *after* building the no-op pads
        self._price_mirror[cols] = new_prices.astype(np.float32)
        return cols_sh, newp_sh

    def reprice(self, deltas: Union[Mapping[Hashable, float],
                                    Sequence[Tuple[Hashable, float]]]
                ) -> int:
        """Apply ``{config_id: new $/h}`` deltas to the sharded
        universe and refresh **every** member's accumulators in one
        collective dispatch; returns #rows whose masked row-minimum
        handed off (synced to host, so a return means the tick's
        collective has completed on every device)."""
        validated = _validated_deltas(self._pos, deltas)
        if validated is None:
            return 0
        cols, new_prices = validated
        with maybe_span(self._metrics, STEP_SPAN):
            cols_sh, newp_sh = self._route_deltas(cols, new_prices)
            (self.d_prices, self.d_cost, self.d_row_best, self.d_norm,
             self.d_scores, moved) = self._step(
                self.d_prices, self.d_cost, self.d_row_best,
                self.d_norm, self.d_scores, self.d_hours, self.d_mask,
                self.d_row_masks,
                jax.device_put(cols_sh, self._spec_d),
                jax.device_put(newp_sh, self._spec_d))
            moved = int(moved)
        self.reprices += 1
        self.dispatches += 1
        return moved

    # -- per-member serving -------------------------------------------------
    def ranking(self, key: Hashable) -> List[RankedConfig]:
        """A member's full sorted ranking under the tolerance contract
        (memoized on the tick count; a fresh list copy per call)."""
        memo = self._ranking_memo.get(key)
        if memo is None or memo[0] != self.reprices:
            slot = self._slot_of(key)
            self.materializations += 1
            if self._c_mat is not None:
                self._c_mat.inc()
            with maybe_span(self._metrics, "rank.materialize"):
                memo = (self.reprices,
                        _materialize(self.scores(key),
                                     self._counts[slot],
                                     self.config_ids))
            self._ranking_memo[key] = memo
        return list(memo[1])

    def top_k(self, key: Hashable, k: int) -> List[RankedConfig]:
        """The head of a member's ranking via per-shard ``lax.top_k``
        plus a deterministic host merge by ``(score, global index)`` —
        element-wise identical to ``ranking(key)[:k]``, ties included
        (DESIGN.md §13 has the argument).

        k is clamped to the catalog size *before* the jitted kernel
        (`k > C` is a serving convenience, never a crash or a
        recompile storm); the per-shard depth is further clamped to
        the shard width, which still guarantees >= k real candidates
        after the merge."""
        slot = self._slot_of(key)
        n_cfgs = len(self.config_ids)
        k = _check_k(k, n_cfgs)
        k_loc = min(k, self._c_loc)
        fn = _sharded_topk_fn(self.n_devices, k_loc, self._c_loc)
        gidx, vals = fn(self.d_scores, self._d_finite,
                        jnp.asarray(slot, dtype=jnp.int32))
        with maybe_span(self._metrics, MERGE_SPAN):
            gidx = np.asarray(gidx)
            vals = np.asarray(vals, dtype=np.float64)
            keep = gidx < n_cfgs           # drop pad-tail candidates
            gidx, vals = gidx[keep], vals[keep]
            order = np.lexsort((gidx, vals))[:k]
        counts = self._counts[slot]
        out = []
        for j in order:
            i = int(gidx[j])
            n = int(counts[i])
            out.append(RankedConfig(
                self.config_ids[i],
                float(vals[j]) if n else float("inf"),
                float(vals[j]) / n if n else float("inf")))
        return out

    def winner(self, key: Hashable) -> RankedConfig:
        """The member's top pick — ``top_k(key, 1)`` on device."""
        return self.top_k(key, 1)[0]
