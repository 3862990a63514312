"""How the float32 matmul precision moves the fleet's scores on a device.

    PYTHONPATH=src python benchmarks/fleet_precision.py

The selector fleet's member-score matmul (``scores = row_masks @ norm``)
at ``chip_smoke.py``'s size: 64 jobs x 10,000 configs, 8 members, 32
ticks of 1% changed prices, all from ``market_bench``'s generators.  On
every tick the numpy norm matrix at the live prices goes through the
matmul on the default device at ``Precision.DEFAULT`` and at
``Precision.HIGHEST``, and each of the S x C score cells is compared
with the numpy float64 product under the jax ``ScoreContract``.  Prints
one line per precision: the largest relative error and the number of
cells outside the contract.  A TPU's DEFAULT rounds float32 operands to
bfloat16; a CPU multiplies in float32 either way.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from market_bench import _delta_batches, _fleet_members, _universe
from repro.launch.compile_cache import enable_compile_cache
from repro.selector import score_contract


def fleet_precision(n_jobs: int = 64, n_cfgs: int = 10_000,
                    n_members: int = 8, n_ticks: int = 32,
                    frac: float = 0.01, seed: int = 0) -> dict:
    """``{precision: (max relative error, cells outside the contract)}``
    over ``n_ticks`` x ``n_members`` x ``n_cfgs`` score cells."""
    hours, mask, prices, ids, rng = _universe(n_jobs, n_cfgs, seed)
    batches = _delta_batches(ids, prices, rng, n_ticks, frac)
    members = _fleet_members(n_jobs, n_members, rng)
    row_masks = np.zeros((n_members, n_jobs), np.float32)
    for s, rows in enumerate(members.values()):
        row_masks[s, rows] = 1.0
    contract = score_contract("jax_batched")
    matmuls = {name: jax.jit(lambda a, b, p=jax.lax.Precision[name]:
                             jnp.matmul(a, b, precision=p))
               for name in ("DEFAULT", "HIGHEST")}
    out = dict.fromkeys(matmuls, (0.0, 0))
    pos = {c: i for i, c in enumerate(ids)}
    live = prices.copy()
    for batch in batches:
        for cid, p in batch.items():
            live[pos[cid]] = p
        cost = np.where(mask, hours * live, np.inf)
        norm = np.where(mask, cost / cost.min(axis=1, keepdims=True), 0.0)
        ref = row_masks.astype(np.float64) @ norm
        for name, matmul in matmuls.items():
            got = np.asarray(matmul(row_masks, norm.astype(np.float32)),
                             np.float64)
            err = np.abs(got - ref)
            scale = np.maximum(np.abs(got), np.abs(ref))
            rel = np.divide(err, scale, out=np.zeros_like(err),
                            where=scale > 0)
            bad = err > contract.abs_tol + contract.rel_tol * scale
            worst, n_bad = out[name]
            out[name] = (max(worst, float(rel.max())),
                         n_bad + int(bad.sum()))
    return out


def main() -> None:
    enable_compile_cache()
    n_ticks, n_members, n_cfgs = 32, 8, 10_000
    platform = jax.devices()[0].platform
    for name, (worst, n_bad) in fleet_precision(
            n_members=n_members, n_cfgs=n_cfgs, n_ticks=n_ticks).items():
        print(f"precision={name} platform={platform} max_rel_err={worst:.3e} "
              f"cells_outside_contract={n_bad} of "
              f"{n_ticks * n_members * n_cfgs}", flush=True)


if __name__ == "__main__":
    main()
