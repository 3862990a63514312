"""The generators: same seed, same bytes; the walk keeps the original's
band and reversion; the copies agree with the program's models."""
from __future__ import annotations

import numpy as np

from gen import deployment as gd
from gen import spark_sim
from gen import submissions as gs
from gen.spot_walk import spot_batches

SEED = 2 ** 31 + 12345


def _data(cell, seed):
    dep = gd.build(cell.config, seed)
    batches = spot_batches(dep.base_prices, dep.spot_cols, dep.region_of_col,
                           dep.n_regions, 60, cell.traffic["ticks"],
                           gd.rng_for(seed, gd.STREAM_WALK))
    stream = gs.stream(cell.traffic["submissions"], len(dep.routes),
                       dep.live0, 20.0, gd.rng_for(seed, gd.STREAM_SUBS))
    arrays = [dep.base_prices, dep.shape_hours, dep.profiled,
              np.asarray(dep.live0), stream.due_s, stream.route,
              stream.retire]
    arrays += [a for b in batches for a in b]
    return b"".join(np.ascontiguousarray(a).tobytes() for a in arrays)


def test_same_seed_same_bytes(tiny):
    for name in ("store.open", "flora_gcp.open"):
        cell = tiny(name)
        assert _data(cell, SEED) == _data(cell, SEED)
        assert _data(cell, SEED) != _data(cell, SEED + 1)


def test_full_size_catalog_shapes():
    import cells
    from conftest import ROOT
    cell = cells.resolve(ROOT, "flora_gcp.saturate")
    dep = gd.build(cell.config, SEED)
    assert dep.n_cfgs == 15360 and dep.spot_cols.size == 7680
    assert dep.profiled.shape == (18, 15360)
    assert len(dep.live0) == len(dep.routes) == 11


def _walk(band: float, factor: float):
    rng = np.random.default_rng(7)
    n_cols, n_regions = 400, 4
    base = rng.uniform(0.5, 5.0, n_cols)
    region = np.arange(n_cols) % n_regions
    walk = {"change_fraction": 0.25, "reversion": 0.15, "volatility": 0.06,
            "band": band, "event_every": 50, "event_first": 2,
            "event_ticks": 25, "event_factor": factor}
    return base, walk, spot_batches(base, np.arange(n_cols), region,
                                    n_regions, 1500, walk,
                                    np.random.default_rng(11))


def test_walk_keeps_band():
    for band, factor in ((1.1, 1.8), (8.0, 1.8)):
        base, walk, batches = _walk(band, factor)
        for cols, new in batches:
            assert np.all(new >= base[cols] / band * (1 - 1e-12))
            assert np.all(new <= base[cols] * band * (1 + 1e-12))


def test_walk_keeps_reversion():
    """Away from event boundaries a quote is log-price reversion toward
    the target plus a shock: regressing the step on the gap to the
    target gives back the reversion rate and the volatility."""
    base, walk, batches = _walk(8.0, 1.0)
    price = base.copy()
    gap, step = [], []
    for t, (cols, new) in enumerate(batches):
        if not (t >= 2 and (t - 2) % 50 in (0, 25)):
            gap.append(np.log(base[cols]) - np.log(price[cols]))
            step.append(np.log(new) - np.log(price[cols]))
        price[cols] = new
    gap, step = np.concatenate(gap), np.concatenate(step)
    slope, icept = np.polyfit(gap, step, 1)
    resid = step - (slope * gap + icept)
    assert abs(slope - walk["reversion"]) < 0.02
    assert abs(icept) < 0.005
    assert abs(resid.std() - walk["volatility"]) < 0.003


def test_runtime_copy_matches_program():
    from repro.core import spark_sim as program
    from repro.core.trace import GCP_CONFIGS, PAPER_JOBS
    nodes = np.asarray([c.scale_out for c in GCP_CONFIGS])
    cores = np.asarray([c.cores_per_node for c in GCP_CONFIGS])
    mem = np.asarray([c.mem_per_node_gib for c in GCP_CONFIGS], float)
    for job in PAPER_JOBS:
        got = spark_sim.runtime_s(job.algorithm, job.dataset_gib, nodes,
                                  cores, mem, np.ones(len(GCP_CONFIGS)))
        want = [program.runtime_s(job, c, noise_sigma=0.0)
                for c in GCP_CONFIGS]
        np.testing.assert_allclose(got, want, rtol=1e-12)


def test_price_copy_matches_program(tiny):
    from repro.core.costmodel import LinearPriceModel
    from repro.core.trace import CloudConfig
    cell = tiny("flora_gcp.open")
    dep = gd.build(cell.config, SEED)
    cat = cell.config["catalog"]
    model = LinearPriceModel()
    for col, cid in enumerate(dep.config_ids):
        shape, rest = cid.split("@")
        _, prov = rest.split("/")
        name, nodes = shape.rsplit("x", 1)
        _, vcpus, mem = next(t for t in cat["machine_types"] if t[0] == name)
        want = model(CloudConfig(0, name, int(nodes), vcpus, mem))
        if prov == "spot":
            want *= cat["spot_factor"]
        assert np.isclose(dep.base_prices[col], want, rtol=1e-12)


def test_stream_bursts_zipf_and_churn(tiny):
    cell = tiny("store.open")
    dep = gd.build(cell.config, SEED)
    mix = dict(cell.traffic["submissions"], rate_per_s=400.0)
    s = gs.stream(mix, len(dep.routes), dep.live0, 40.0,
                  np.random.default_rng(3))
    at = mix["burst_at_s"]
    in_burst = ((s.due_s - at) % mix["burst_every_s"] < mix["burst_s"]) \
        & (s.due_s >= at)
    burst_rate = in_burst.sum() / (4 * mix["burst_s"])
    calm_rate = (~in_burst).sum() / (40.0 - 4 * mix["burst_s"])
    assert 3.5 < burst_rate / calm_rate < 6.5
    assert np.all(np.diff(s.due_s) >= 0)
    live = set(dep.live0)
    for r, x in zip(s.route, s.retire):
        if x >= 0:
            assert x in live and r not in live
            live.remove(x)
            live.add(int(r))
        assert r in live
        assert len(live) == len(dep.live0)
    assert live == set(s.live_end)
    assert 0 < (s.retire >= 0).sum() < 0.1 * s.route.size
