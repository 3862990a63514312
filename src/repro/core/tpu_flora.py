"""Flora over TPU mesh configurations — the framework integration.

The paper's pipeline maps 1:1 onto TPU cluster selection (DESIGN.md §3):

* a *cloud configuration* is a :class:`MeshOption` — a TPU slice (chip
  count, generation, $/chip-hour market) plus the mesh split (data vs
  model parallel axes);
* a *test job* is an (architecture x input shape) workload whose "runtime"
  is the roofline-model step time derived from the compiled dry-run
  artifact (this container has no TPU, so the dry-run IS the profiler;
  on real hardware the same trace would hold measured step times);
* *job classes* follow the paper's data-access-pattern split:
  class A (**memory-demanding / state-resident**): decode and long-context
  serving, whose KV-cache/recurrent state must stay HBM-resident;
  class B (**memory-yielding / streaming-compute**): training and prefill,
  which stream activations through the MXU.

Selection routes through :mod:`repro.selector` — the same
catalog/store/rank/service stack as the GCP side (the paper's
normalized-cost ranking is class- and substrate-agnostic), via a
:class:`repro.selector.TpuSliceCatalog` over the mesh options.
"""
from __future__ import annotations

import dataclasses
import json
import re
from typing import List, Mapping, Optional, Sequence, Tuple

from repro.core.costmodel import TpuPriceModel
from repro.core.trace import JobClass
from repro.selector import (ProfilingStore, RankedConfig, SelectionService,
                            TpuSliceCatalog)


@dataclasses.dataclass(frozen=True)
class MeshOption:
    """One selectable TPU deployment: slice size x mesh split."""

    name: str               # e.g. "v5e-256 dp16xtp16"
    generation: str         # "v5e" | "v5p"
    chips: int
    mesh_shape: Tuple[int, ...]
    mesh_axes: Tuple[str, ...]

    def hourly_cost(self, price: TpuPriceModel) -> float:
        return price.slice_hour(self.generation, self.chips)


#: Which shapes belong to which class (user-overridable, like the paper's
#: user annotation step).
SHAPE_CLASSES: Mapping[str, JobClass] = {
    "train_4k": JobClass.B,      # streaming compute: FLOP-bound
    "prefill_32k": JobClass.B,   # streaming compute: FLOP-bound
    "decode_32k": JobClass.A,    # state-resident: KV-cache bandwidth-bound
    "long_500k": JobClass.A,     # state-resident: long-context decode
}


def classify_workload(shape_name: str,
                      annotation: Optional[JobClass] = None) -> JobClass:
    """Step 1 — classification.  ``annotation`` models the user label."""
    if annotation is not None:
        return annotation
    return SHAPE_CLASSES[shape_name]


@dataclasses.dataclass(frozen=True)
class WorkloadRecord:
    """One profiled cell: (arch, shape) on a mesh option -> step seconds."""

    arch: str
    shape: str
    mesh: str
    step_seconds: float     # roofline step time (or measured, on hardware)
    steps: int = 1          # steps per job (scales runtime, not ranking)

    @property
    def job_id(self) -> str:
        return f"{self.arch}:{self.shape}"

    @property
    def job_class(self) -> JobClass:
        return SHAPE_CLASSES[self.shape]


def make_service(options: Sequence[MeshOption],
                 records: Sequence[WorkloadRecord],
                 price: TpuPriceModel,
                 backend: str = "numpy") -> SelectionService:
    """Wire catalog + store + price into a TPU-side selection service
    on the ranking ``backend``."""
    return SelectionService(
        TpuSliceCatalog(options, price),
        ProfilingStore.from_workload_records(
            records, config_ids=[o.name for o in options]),
        price, classifier=lambda shape: classify_workload(str(shape)),
        backend=backend)


class TpuFlora:
    """Flora Steps 0-2 over TPU mesh options (adapter over the service)."""

    def __init__(self, options: Sequence[MeshOption],
                 records: Sequence[WorkloadRecord],
                 price: TpuPriceModel, *, one_class: bool = False):
        self.options = list(options)
        self.records = list(records)
        self.price = price
        self.one_class = one_class
        self._by_name = {o.name: o for o in self.options}
        # paper-faithful adapter: pinned to the float64 bit-stable
        # backend (legacy-loop parity), like repro.core.flora.Flora
        self.service = make_service(self.options, self.records, price,
                                    backend="numpy")

    def rank(self, job_class: JobClass,
             exclude_archs: Sequence[str] = ()) -> List[RankedConfig]:
        klass = None if self.one_class else job_class
        return list(self.service.rank(job_class=klass,
                                      exclude_groups=tuple(exclude_archs)))

    def select(self, shape_name: str, *,
               annotation: Optional[JobClass] = None,
               exclude_archs: Sequence[str] = ()) -> MeshOption:
        """Full pipeline for a submitted (new) workload.

        ``exclude_archs`` enforces the paper's no-recurrence discipline:
        the submitted architecture's own profiling data is not consulted.
        """
        decision = self.service.submit(
            shape_name,
            annotation=annotation if not self.one_class else None,
            exclude_groups=tuple(exclude_archs),
            one_class=self.one_class)
        return self._by_name[decision.config_id]


# --- trace I/O (written by launch/dryrun.py, read by launch/train.py) ---------

def records_from_dryrun_report(report: Mapping) -> List[WorkloadRecord]:
    """Convert a dryrun.py JSON report into profiling records.

    The roofline step time is ``max(compute, memory, collective)`` seconds
    per step — the bound the compiled artifact proves.
    """
    out = []
    for cell in report.get("cells", []):
        if not cell.get("ok"):
            continue
        roof = cell["roofline"]
        step = max(roof["compute_s"], roof["memory_s"], roof["collective_s"])
        out.append(WorkloadRecord(arch=cell["arch"], shape=cell["shape"],
                                  mesh=cell["mesh"], step_seconds=step))
    return out


def load_records(path: str) -> List[WorkloadRecord]:
    with open(path) as f:
        return records_from_dryrun_report(json.load(f))


def _mesh_topology(name: str, chips: int) -> Tuple[Tuple[int, ...],
                                                   Tuple[str, ...]]:
    """Recover (shape, axes) from a ``dp{N}xtp{M}`` mesh name
    (the convention of :func:`repro.launch.mesh.mesh_options`); fall back
    to a pure data-parallel topology for unrecognized names."""
    m = re.fullmatch(r"dp(\d+)xtp(\d+)", name)
    if m:
        return (int(m.group(1)), int(m.group(2))), ("data", "model")
    return (chips,), ("data",)


def service_from_dryrun_report(report: Mapping, price: TpuPriceModel,
                               *, generation: str = "v5e", chips: int = 256
                               ) -> SelectionService:
    """One-call bridge: dryrun JSON -> catalog + store -> service.

    Mesh options are synthesised from the mesh names present in the report
    (the dry-run profiled exactly those splits); their topology is
    recovered from the ``dp{N}xtp{M}`` naming convention where possible.
    """
    recs = records_from_dryrun_report(report)
    meshes = sorted({r.mesh for r in recs})
    options = [MeshOption(m, generation, chips, *_mesh_topology(m, chips))
               for m in meshes]
    return make_service(options, recs, price)
