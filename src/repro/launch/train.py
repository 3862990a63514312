"""Production training launcher.

``--auto-mesh`` runs the paper's pipeline end-to-end: classify the workload
(train -> class B), rank the profiled mesh options from the dry-run trace
under current chip prices, and launch on the winner.  On this CPU container
the launcher runs reduced configs (same code path); on hardware the same
entrypoint drives the full configs.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-1.7b \
        --steps 100 --reduced --auto-mesh --report dryrun_single.json
"""
from __future__ import annotations

import argparse
import json
import os

import jax
import jax.numpy as jnp

import repro.configs as configs
from repro.configs import shapes as shapes_lib
from repro.core.costmodel import TpuPriceModel
from repro.core.tpu_flora import service_from_dryrun_report
from repro.data import pipeline as data_lib
from repro.launch.compile_cache import enable_compile_cache
from repro.models import build_model, count_params
from repro.models.types import ShapeSpec
from repro.train.checkpoint import Checkpointer
from repro.train.train_loop import (StragglerWatchdog, TrainConfig,
                                    make_train_step, train_loop)


def select_mesh(report_path: str, market: str) -> str:
    """Rank the dry-run-profiled meshes via the selection service."""
    with open(report_path) as f:
        report = json.load(f)
    service = service_from_dryrun_report(report, TpuPriceModel(market))
    decision = service.submit("train_4k")
    print(f"[flora] class {decision.job_class.value} (streaming-compute) "
          f"-> mesh {decision.config_id} at {decision.hourly_cost:.2f} $/h")
    return str(decision.config_id)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-1.7b")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--reduced", action="store_true",
                    help="train the reduced (CPU-sized) config")
    ap.add_argument("--d-model", type=int, default=None,
                    help="override reduced width (e.g. ~100M model)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--auto-mesh", action="store_true")
    ap.add_argument("--report", default="dryrun_single.json")
    ap.add_argument("--market", default="ondemand",
                    choices=["ondemand", "spot"])
    ap.add_argument("--microbatches", type=int, default=1)
    args = ap.parse_args()
    enable_compile_cache()

    if args.auto_mesh and os.path.exists(args.report):
        select_mesh(args.report, args.market)

    cfg = configs.get(args.arch)
    if args.reduced:
        kw = {}
        if args.d_model:
            kw["d_model"] = args.d_model
        cfg = configs.reduced(cfg, **kw)
    model = build_model(cfg)
    n = count_params(model.param_specs())
    print(f"[train] {cfg.name}: {n/1e6:.1f}M params, "
          f"{cfg.num_layers} layers, d_model={cfg.d_model}")

    shape = ShapeSpec("cli", args.seq, args.batch, "train")
    stream = data_lib.for_model(cfg, shape)
    tcfg = TrainConfig(peak_lr=args.lr, warmup_steps=10,
                       total_steps=args.steps,
                       microbatches=args.microbatches)
    step_fn, opt = make_train_step(model, tcfg)

    ckpt = Checkpointer(args.ckpt_dir) if args.ckpt_dir else None
    start = 0
    params = model.init(jax.random.PRNGKey(0))
    opt_state = opt.init(params)
    if ckpt and args.resume and ckpt.latest_step() is not None:
        tree, start = ckpt.restore({"params": params,
                                    "opt_state": opt_state})
        params, opt_state = tree["params"], tree["opt_state"]
        print(f"[train] resumed from step {start}")

    watchdog = StragglerWatchdog()
    batches = iter(data_lib.PrefetchIterator(stream, start_step=start))
    params, opt_state, hist = train_loop(
        model, tcfg, params, opt_state, batches, steps=args.steps,
        checkpointer=ckpt, checkpoint_every=args.ckpt_every,
        watchdog=watchdog, start_step=start, train_step=step_fn)
    if ckpt:
        ckpt.save(args.steps, params, opt_state, block=True)
    print(f"[train] done: loss {hist['loss'][0]:.3f} -> "
          f"{hist['loss'][-1]:.3f} over {len(hist['loss'])} steps; "
          f"straggler events: {len(watchdog.events)}")


if __name__ == "__main__":
    main()
