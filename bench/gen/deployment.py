"""Build a deployment's data from its file and a seed.

The catalog is GCP cluster shapes (machine type x scale-out) in every
region under every provisioning model.  One shape has one runtime in every
region and provisioning column; prices are ``LinearPriceModel``'s N2
rates (a copy: total vCPUs x $/vCPU-hour + total GiB x $/GiB-hour), the
same in every region, times the spot factor on spot columns.

Jobs come either from a table in the file (the paper's Table I) or from
tenants x algorithms x dataset sizes drawn from the seed (a collaborative
store).  A route is a (job class, excluded groups) selection, the unit the
serving front end publishes one top-k head for.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Tuple

import numpy as np

from gen import spark_sim

# seed streams: every generator draws from its own stream of the seed
STREAM_JOBS, STREAM_NOISE, STREAM_ROUTES, STREAM_WALK, STREAM_SUBS = range(5)


def rng_for(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), stream])


@dataclasses.dataclass
class Deployment:
    """Everything the benchmark generates for one deployment and seed."""

    spec: dict
    config_ids: List[str]
    base_prices: np.ndarray          # (C,) float64 $/h
    shape_of_col: np.ndarray         # (C,) int32
    region_of_col: np.ndarray        # (C,) int32
    spot_cols: np.ndarray            # column indices of spot columns
    n_regions: int
    job_ids: List[str]
    job_class: List[str]             # "A" | "B"
    job_group: List[str]
    shape_hours: np.ndarray          # (J, n_shapes) float64 runtime hours
    profiled: np.ndarray             # (J, P) int32 profiled columns per job
    routes: List[Tuple[Optional[str], Tuple[str, ...]]]
    live0: List[int]                 # route indices live after set-up

    @property
    def n_cfgs(self) -> int:
        return len(self.config_ids)

    @property
    def n_jobs(self) -> int:
        return len(self.job_ids)

    def hours_of(self) -> np.ndarray:
        """(J, P) runtime hours of every profiled cell."""
        shapes = self.shape_of_col[self.profiled]
        return np.take_along_axis(self.shape_hours, shapes, axis=1)

    def rows_of(self, route) -> np.ndarray:
        """Job rows a route ranks over: the store's ``select_jobs`` rule."""
        klass, excl = route
        return np.asarray([j for j in range(self.n_jobs)
                           if (klass is None or self.job_class[j] == klass)
                           and self.job_group[j] not in excl],
                          dtype=np.int64)


def _catalog(spec: dict):
    cat = spec["catalog"]
    types = cat["machine_types"]            # [name, vcpus, mem_gib]
    scale_outs = cat["scale_outs"]
    regions = cat["regions"]                # names
    provisioning = cat["provisioning"]      # on-demand first, then spot
    n_shapes = len(types) * len(scale_outs)
    ids, base, shape_of, region_of, spot = [], [], [], [], []
    for r, region in enumerate(regions):
        for prov in provisioning:
            for t, (name, vcpus, mem) in enumerate(types):
                for s, nodes in enumerate(scale_outs):
                    col = len(ids)
                    ids.append(f"{name}x{nodes}@{region}/{prov}")
                    price = nodes * (vcpus * cat["cpu_core_hour"]
                                     + mem * cat["mem_gib_hour"])
                    if prov == "spot":
                        price *= cat["spot_factor"]
                        spot.append(col)
                    base.append(price)
                    shape_of.append(t * len(scale_outs) + s)
                    region_of.append(r)
    nodes = np.asarray([n for _ in types for n in scale_outs])
    cores = np.asarray([v for _, v, _ in types for _ in scale_outs])
    mem = np.asarray([m for _, _, m in types for _ in scale_outs],
                     dtype=np.float64)
    return (ids, np.asarray(base), np.asarray(shape_of, np.int32),
            np.asarray(region_of, np.int32), np.asarray(spot, np.int64),
            len(regions), n_shapes, nodes, cores, mem)


def _jobs(spec: dict, rng: np.random.Generator, n_shapes: int):
    """[(job id, algorithm, dataset GiB, class, group, profiled shapes)]"""
    js = spec["jobs"]
    out = []
    if js["kind"] == "table":
        for algo, _dtype, gib, klass in js["table"]:
            out.append((f"{algo}/{gib:g}GiB", algo, float(gib), klass, algo,
                        np.arange(n_shapes)))
        return out
    if js["kind"] != "tenants":
        raise ValueError(f"unknown jobs kind {js['kind']!r}")
    per_job = js["profiled_shapes_per_job"]
    for t in range(js["tenants"]):
        for algo, klass, (lo, hi) in js["algorithms"]:
            group = f"t{t:02d}/{algo}"
            for i in range(js["jobs_per_group"]):
                gib = float(np.exp(rng.uniform(np.log(lo), np.log(hi))))
                shapes = np.sort(rng.choice(n_shapes, per_job,
                                            replace=False))
                out.append((f"{group}/{i}", algo, gib, klass, group, shapes))
    return out


def _routes(spec: dict, classes: List[str], groups: List[str],
            group_class: dict, rng: np.random.Generator):
    rs = spec["routes"]
    if rs["kind"] == "own_group_out":
        # the paper's leave-one-algorithm-out selections, plus each class
        # with nothing excluded
        routes = [(group_class[g], (g,)) for g in groups]
        routes += [(c, ()) for c in classes]
    elif rs["kind"] == "class_x_group":
        routes = [(c, (g,)) for c in classes for g in groups]
    else:
        raise ValueError(f"unknown routes kind {rs['kind']!r}")
    live = rs.get("live", len(routes))
    live0 = sorted(rng.choice(len(routes), live, replace=False).tolist()) \
        if live < len(routes) else list(range(len(routes)))
    return routes, live0


def build(spec: dict, seed: int) -> Deployment:
    (ids, base, shape_of, region_of, spot, n_regions, n_shapes, nodes,
     cores, mem) = _catalog(spec)
    jobs = _jobs(spec, rng_for(seed, STREAM_JOBS), n_shapes)
    noise_rng = rng_for(seed, STREAM_NOISE)
    hours = np.empty((len(jobs), n_shapes))
    for j, (_, algo, gib, _, _, _) in enumerate(jobs):
        noise = np.exp(spark_sim.NOISE_SIGMA
                       * noise_rng.standard_normal(n_shapes))
        hours[j] = spark_sim.runtime_s(algo, gib, nodes, cores, mem,
                                       noise) / 3600.0
    cols_of_shape = [np.flatnonzero(shape_of == s) for s in range(n_shapes)]
    profiled = np.stack([np.sort(np.concatenate(
        [cols_of_shape[s] for s in job[5]])) for job in jobs]
    ).astype(np.int32)
    groups = list(dict.fromkeys(job[4] for job in jobs))
    group_class = {job[4]: job[3] for job in jobs}
    classes = sorted(set(group_class.values()))
    routes, live0 = _routes(spec, classes, groups, group_class,
                            rng_for(seed, STREAM_ROUTES))
    return Deployment(spec=spec, config_ids=ids, base_prices=base,
                      shape_of_col=shape_of, region_of_col=region_of,
                      spot_cols=spot, n_regions=n_regions,
                      job_ids=[job[0] for job in jobs],
                      job_class=[job[3] for job in jobs],
                      job_group=[job[4] for job in jobs],
                      shape_hours=hours, profiled=profiled, routes=routes,
                      live0=live0)
