"""CPU tests of the benchmark: ``python -m pytest bench``.

They run at tiny sizes (a catalog of 48 configurations, a few jobs) with
JAX on the CPU; nothing here measures speed.
"""
from __future__ import annotations

import pathlib
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


#: a test-only store of many tenants (the generator's ``tenants`` jobs
#: and ``class_x_group`` routes), so that route churn and the forwarded
#: control path run on the CPU although no cell of the benchmark has them
STORE_JOBS = {"kind": "tenants", "tenants": 4, "jobs_per_group": 2,
              "profiled_shapes_per_job": 3,
              "algorithms": [["Sort", "A", [20, 400]],
                             ["Grep", "B", [100, 6000]],
                             ["KMeans", "A", [20, 400]]]}
STORE_ROUTES = {"kind": "class_x_group", "live": 8}


def shrink(cell):
    """The cell's own files cut to a size the CPU runs in seconds."""
    cat = cell.config["catalog"]
    cat["machine_types"] = cat["machine_types"][:3]
    cat["scale_outs"] = [2, 4]
    cat["regions"] = cat["regions"][:4]
    cell.config["knee_ticks_per_s"] = 40
    ticks = cell.traffic["ticks"]
    # a pool far shorter than a run's ticks, so the replay wraps
    ticks.update(change_fraction=0.1, event_every=10, event_ticks=5,
                 pool=60)
    if cell.traffic.get("submissions"):
        cell.traffic["submissions"].update(rate_per_s=150,
                                           new_route_share=0.05)
    return cell


#: what the open mix reports, for test cells under it (no cell of the
#: benchmark runs the open mix until its knee is measured on a chip)
OPEN_E2E = [{"name": "staleness_p95_ms", "unit": "ms"},
            {"name": "decision_p95_ms", "unit": "ms"},
            {"name": "setup_s", "unit": "s"}]


def resolve(name):
    """A cell of ``BENCHMARK.json``; ``flora_gcp.open``, built from its
    files; or ``store.<mix>``: the test-only store under that mix."""
    import cells
    config, mix = name.split(".", 1)
    if config != "store" and name != "flora_gcp.open":
        return cells.resolve(ROOT, name)
    if mix == "open":
        cell = cells.Cell(name=name, chips=1,
                          config=cells._json(BENCH / "deployments"
                                             / "flora_gcp.json"),
                          traffic=cells._json(BENCH / "traffic" / "open.json"),
                          e2e=OPEN_E2E, layer=[])
    else:
        cell = cells.resolve(ROOT, f"flora_gcp.{mix}")
    if config == "store":
        cell.config.update(name="store", jobs=dict(STORE_JOBS),
                           routes=dict(STORE_ROUTES))
    return cell


@pytest.fixture
def tiny():
    def make(name):
        return shrink(resolve(name))
    return make
