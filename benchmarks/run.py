"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows: ``us_per_call`` is the wall
time of one full experiment computation (the paper's headline claim is that
Flora's *selection overhead is negligible* — milliseconds); ``derived`` is
the experiment's headline number(s).  The same rows are written as
machine-readable ``BENCH_selector.json`` (override the path with the
``BENCH_SELECTOR_JSON`` env var) so CI can track the perf trajectory.
"""
from __future__ import annotations

import json
import os
import sys
import time

from repro.launch.compile_cache import enable_compile_cache
from repro.core import costmodel, evaluate, spark_sim
from repro.core.flora import Flora
from repro.core.trace import JobClass, PAPER_JOBS

from _bench_io import BenchRows

ROWS = BenchRows("BENCH_SELECTOR_JSON", "BENCH_selector.json")
emit = ROWS.emit
write_json = ROWS.write_json



def _timed(fn, *args, repeat: int = 1, **kw):
    t0 = time.perf_counter()
    out = None
    for _ in range(repeat):
        out = fn(*args, **kw)
    dt = (time.perf_counter() - t0) / repeat
    return out, dt * 1e6


def bench_table3_trace_stats(trace, price):
    stats, us = _timed(trace.stats, price)
    derived = (f"cost_mean={stats['cost_usd']['mean']:.3f};"
               f"rt_mean={stats['runtime_s']['mean']:.0f};"
               f"rt_max={stats['runtime_s']['max']:.0f}"
               f" (paper: 1.409/1835/21715)")
    emit("table3_trace_stats", us, derived)


def bench_table4_selection(trace, price):
    results, us = _timed(evaluate.table4, trace, price)
    by = {r.name: r for r in results}
    derived = ";".join(
        f"{name}={by[name].mean_norm_cost:.3f}"
        for name in ("Flora", "Flora with one class", "Juggler", "Crispy"))
    derived += " (paper: Flora=1.052;Fw1C=1.336;Juggler=1.334;Crispy=1.384)"
    emit("table4_selection", us, derived)


def bench_table5_perjob(trace, price):
    t5, us = _timed(evaluate.table5, trace, price)
    flora = t5["Flora"]
    worst = max(r.norm_cost for r in flora.per_job)
    a_picks = {r.selection.index for r in flora.per_job
               if r.job.job_class is JobClass.A}
    b_picks = {r.selection.index for r in flora.per_job
               if r.job.job_class is JobClass.B}
    derived = (f"flora_mean={flora.mean_norm_cost:.3f};max={worst:.3f};"
               f"classA_picks={sorted(a_picks)};classB_picks={sorted(b_picks)}"
               f" (paper: A->9, B->1, mean 1.052)")
    emit("table5_perjob", us, derived)


def bench_fig2_price_sweep(trace, price):
    ratios = [10 ** (-2 + 3 * i / 24) for i in range(25)]
    curves, us = _timed(evaluate.fig2_price_sweep, trace, price, ratios)
    always_best = all(
        curves["Flora"][i] <= min(v[i] for k, v in curves.items()
                                  if k != "Flora") + 1e-9
        for i in range(len(ratios)))
    derived = (f"points={len(ratios)};"
               f"flora_max_over_sweep={max(curves['Flora']):.3f};"
               f"flora_always_best={always_best}")
    emit("fig2_price_sweep", us, derived)


def bench_fig3_misclassification(trace, price):
    fracs = [i / 20 for i in range(21)]
    curves, us = _timed(evaluate.fig3_misclassification, trace, price, fracs)
    x, us2 = _timed(evaluate.crossover_fraction, trace, price)
    derived = (f"crossover_vs_fw1c={x:.3f} (paper: ~1/3);"
               f"coinflip={curves['Flora'][10]:.3f};"
               f"random={curves['random selection'][0]:.3f}")
    emit("fig3_misclassification", us + us2, derived)


def bench_selection_overhead(trace, price):
    """§III-B: per-selection overhead 'in the millisecond range'."""
    flora = Flora(trace, price)
    job = PAPER_JOBS[0]
    _, us = _timed(lambda: flora.select_for_job(job), repeat=200)
    emit("selection_overhead", us,
         f"paper_claims_milliseconds={us < 10_000}")


def bench_tpu_selection():
    """DESIGN.md §3: mesh selection over the dry-run-profiled trace."""
    from repro.core.costmodel import TpuPriceModel
    from repro.core.tpu_flora import service_from_dryrun_report
    path = os.environ.get("DRYRUN_REPORT", "dryrun_single.json")
    if not os.path.exists(path):
        emit("tpu_selection", 0.0, "skipped=no_dryrun_report")
        return
    with open(path) as f:
        report = json.load(f)
    service = service_from_dryrun_report(report, TpuPriceModel())
    if not len(service.store) or not len(service.catalog):
        emit("tpu_selection", 0.0, "skipped=empty_report")
        return
    pick, us = _timed(lambda: service.submit("decode_32k"))
    emit("tpu_selection", us,
         f"decode_pick={pick.config_id};records={len(service.store)};"
         f"cached={pick.from_cache}")


def bench_rank_vectorized_vs_dict():
    """Tentpole acceptance: vectorized rank beats the per-pair dict loop
    from ~1k (job x config) cells (see benchmarks/rank_bench.py for the
    full sweep)."""
    import rank_bench
    for n_jobs, n_cfgs in ((50, 20), (200, 50)):
        r = rank_bench.compare(n_jobs, n_cfgs, repeat=10)
        emit(f"rank_vectorized_{n_jobs}x{n_cfgs}", r["us_numpy"],
             f"cells={r['cells']};dict_loop_us={r['us_dict']:.1f};"
             f"speedup={r['speedup']:.1f}x;"
             f"vectorized_wins={r['us_numpy'] < r['us_dict']}")


def main() -> None:
    enable_compile_cache()
    t0 = time.time()
    trace = spark_sim.generate_trace(seed=0)
    price = costmodel.LinearPriceModel()
    print("name,us_per_call,derived")
    bench_table3_trace_stats(trace, price)
    bench_table4_selection(trace, price)
    bench_table5_perjob(trace, price)
    bench_fig2_price_sweep(trace, price)
    bench_fig3_misclassification(trace, price)
    bench_selection_overhead(trace, price)
    bench_tpu_selection()
    bench_rank_vectorized_vs_dict()
    write_json()
    if "--with-replay" in sys.argv:
        # the dynamic-price counterpart of the Fig. 2 rows above: replay
        # the bundled recorded history, audit the journal, score vs the
        # oracles (writes its own BENCH_replay.json; exits 1 on mismatch)
        import replay_bench
        replay_bench.main(smoke=True)
    print(f"# total {time.time() - t0:.1f}s", file=sys.stderr)


if __name__ == "__main__":
    main()
