"""Test-job executions: the store's first profile and the records that
arrive while it serves.

Flora learns a job category's best cluster from executions of test jobs
(arXiv 2502.21046, §II-III): its dataset is each of Table I's jobs run
on Table II's 10 N2 configurations.  Here each job starts profiled on
``initial_shapes_per_job`` cluster shapes drawn from the seed among the
shapes of Table II's span (N2 types of ``vcpus`` vCPUs at ``nodes``
nodes); one shape's run fills every region and provisioning column of
that shape, as in ``gen.deployment``.  Its runtimes are the
deployment's own.

A record is one more execution: one job (uniform over the jobs) run on
one shape, with a fresh noise draw of ``gen.spark_sim``'s model.  A
share ``new_shape_share`` runs a shape of the span the job has not
profiled yet (a re-run once the job has profiled every shape of the
span); the rest re-run a shape it has.  The pool of records is replayed
cyclically, like the price batches, so a record carries the same cells
every time it is replayed.
"""
from __future__ import annotations

import dataclasses
from typing import List

import numpy as np

from gen import deployment as gendep
from gen import spark_sim

# seed streams after gen.deployment's
STREAM_PROFILE, STREAM_RECORDS = 5, 6


@dataclasses.dataclass(frozen=True)
class Record:
    job: int                 # row of the deployment's jobs
    shape: int               # cluster shape (machine type x scale-out)
    hours: float             # runtime hours, on every column of the shape


def _shapes(spec: dict):
    """(nodes, vCPUs per node, GiB per node) of every shape, in
    ``gen.deployment``'s order."""
    cat = spec["catalog"]
    types, outs = cat["machine_types"], cat["scale_outs"]
    nodes = np.asarray([n for _ in types for n in outs])
    cores = np.asarray([v for _, v, _ in types for _ in outs])
    mem = np.asarray([m for _, _, m in types for _ in outs],
                     dtype=np.float64)
    return nodes, cores, mem


def eligible(spec: dict) -> np.ndarray:
    """Shapes of the span the profile draws from."""
    prof = spec["profiling"]
    nodes, cores, _ = _shapes(spec)
    (v_lo, v_hi), (n_lo, n_hi) = prof["vcpus"], prof["nodes"]
    return np.flatnonzero((cores >= v_lo) & (cores <= v_hi)
                          & (nodes >= n_lo) & (nodes <= n_hi))


def initial(spec: dict, n_jobs: int, seed: int) -> np.ndarray:
    """(J, initial_shapes_per_job) sorted shapes each job starts on."""
    span = eligible(spec)
    n = spec["profiling"]["initial_shapes_per_job"]
    if not 0 < n <= span.size:
        raise ValueError(f"{n} initial shapes from a span of {span.size}")
    rng = gendep.rng_for(seed, STREAM_PROFILE)
    return np.stack([np.sort(rng.choice(span, n, replace=False))
                     for _ in range(n_jobs)])


def pool(spec: dict, records: dict, start: np.ndarray,
         seed: int) -> List[Record]:
    """``records["pool"]`` records in tick order, from the profile
    ``start`` (what ``initial`` drew) onward."""
    table = spec["jobs"]["table"]
    nodes, cores, mem = _shapes(spec)
    span = eligible(spec)
    profiled = [set(row.tolist()) for row in start]
    rng = gendep.rng_for(seed, STREAM_RECORDS)
    out: List[Record] = []
    for _ in range(records["pool"]):
        j = int(rng.integers(len(table)))
        fresh = [s for s in span.tolist() if s not in profiled[j]]
        if rng.random() < records["new_shape_share"] and fresh:
            s = int(rng.choice(fresh))
            profiled[j].add(s)
        else:
            s = int(rng.choice(sorted(profiled[j])))
        algo, _dtype, gib, _klass = table[j]
        noise = np.exp(spark_sim.NOISE_SIGMA * rng.standard_normal(1))
        secs = spark_sim.runtime_s(algo, gib, nodes[s:s + 1],
                                   cores[s:s + 1], mem[s:s + 1], noise)
        out.append(Record(j, s, float(secs[0]) / 3600.0))
    return out
