"""Runtime of a Spark job on a GCP N2 cluster shape, vectorised over shapes.

A copy of the analytical model in ``repro.core.spark_sim.runtime_s``
(same constants, same terms), kept with the benchmark so that a change to
the program cannot move the data the benchmark ranks.  The noise term is
a log-normal draw from the caller's seeded generator instead of an md5
hash, so that one call fills a whole (job x shape) matrix.
"""
from __future__ import annotations

import dataclasses

import numpy as np

GCS_BW_PER_CORE = 0.030
GCS_BW_CORE_CAP = 8
GCS_BW_NODE_BASE = 0.050
GCS_BW_CLUSTER_CAP = 2.2
DISK_BW_PER_CORE = 0.015
DISK_BW_NODE_BASE = 0.090
NET_BW_PER_CORE = 0.020
NET_BW_NODE_BASE = 0.110
CLUSTER_SCALING = 0.85
CACHE_FRACTION = 0.58
NODE_MEM_OVERHEAD_GIB = 2.0
GC_HEAP_KNEE_GIB = 16.0
GC_PENALTY_PER_GIB = 0.002
CORE_EFF_EXPONENT = 0.06
STARTUP_BASE_S = 70.0
STARTUP_PER_NODE_S = 0.5
THRASH_CPU_FACTOR = 6.0
SPILL_CPU_FACTOR = 1.0
SPILL_IO_PASSES = 4.0
REPARSE_FACTOR = 1.5
NOISE_SIGMA = 0.08


@dataclasses.dataclass(frozen=True)
class AlgoParams:
    w: float
    parse_w: float
    iters: int
    kappa: float
    shuffle: float
    out: float
    storage: str
    kappa_peak: float


ALGO_PARAMS = {
    "Grep":               AlgoParams(8, 6, 1, 0.00, 0.002, 0.010, "none", 0.08),
    "Sort":               AlgoParams(22, 8, 1, 1.05, 2.000, 1.000, "disk", 1.20),
    "WordCount":          AlgoParams(100, 10, 1, 0.00, 0.050, 0.020, "none", 0.25),
    "KMeans":             AlgoParams(32, 16, 10, 1.10, 0.010, 0.001, "mem", 1.15),
    "LinearRegression":   AlgoParams(20, 16, 8, 0.55, 0.010, 0.001, "mem", 0.60),
    "LogisticRegression": AlgoParams(22, 16, 9, 0.65, 0.010, 0.001, "mem", 0.70),
    "Join":               AlgoParams(24, 8, 1, 0.75, 2.200, 0.300, "disk", 0.90),
    "GroupByCount":       AlgoParams(30, 8, 1, 0.00, 0.020, 0.001, "none", 0.20),
    "SelectWhereOrderBy": AlgoParams(18, 8, 1, 0.04, 0.040, 0.030, "disk", 0.12),
}


def runtime_s(algorithm: str, dataset_gib: float, nodes: np.ndarray,
              cores_per_node: np.ndarray, mem_per_node_gib: np.ndarray,
              noise: np.ndarray) -> np.ndarray:
    """Modelled runtime in seconds on every shape; ``noise`` is the
    log-normal multiplier per shape."""
    p = ALGO_PARAMS[algorithm]
    s = float(dataset_gib)
    n = nodes.astype(np.float64)
    k = cores_per_node.astype(np.float64)
    mem = mem_per_node_gib.astype(np.float64)
    scale = n ** CLUSTER_SCALING
    gcs = np.minimum((GCS_BW_PER_CORE * np.minimum(k, GCS_BW_CORE_CAP)
                      + GCS_BW_NODE_BASE) * scale, GCS_BW_CLUSTER_CAP)
    disk = (DISK_BW_PER_CORE * k + DISK_BW_NODE_BASE) * scale
    net = (NET_BW_PER_CORE * k + NET_BW_NODE_BASE) * scale
    cores_eff = n * k * (8.0 / k) ** CORE_EFF_EXPONENT
    heap = np.maximum(1.0, mem - NODE_MEM_OVERHEAD_GIB)
    gc = np.ones_like(n)
    if p.kappa > 0:
        gc = gc + GC_PENALTY_PER_GIB * np.maximum(0.0, heap - GC_HEAP_KNEE_GIB)

    t = STARTUP_BASE_S + STARTUP_PER_NODE_S * n
    t = t + s / gcs + p.out * s / gcs
    if p.shuffle > 0:
        t = t + p.shuffle * s / net + 0.5 * p.shuffle * s / disk
    cpu = (p.parse_w * s + p.w * s * p.iters) / cores_eff

    need = p.kappa * s
    if need > 0:
        avail = CACHE_FRACTION * np.maximum(
            0.0, mem - NODE_MEM_OVERHEAD_GIB) * n
        miss = np.maximum(0.0, need - avail)
        mf = miss / need
        reload_passes = max(0, p.iters - 1)
        if p.storage == "mem":
            vol = miss * mf * reload_passes
            t = t + vol / gcs
            cpu = (cpu + vol * REPARSE_FACTOR * p.parse_w / cores_eff) \
                * (1.0 + THRASH_CPU_FACTOR * mf ** 4)
        elif p.storage == "disk":
            vol = miss * mf * SPILL_IO_PASSES * max(1, reload_passes)
            t = t + vol / disk
            cpu = cpu * (1.0 + SPILL_CPU_FACTOR * mf ** 2)
    t = t + cpu * gc
    return t * noise
