"""Perf hillclimbing driver (EXPERIMENTS.md §Perf).

Re-lowers a chosen cell under named variants (sharding rules, mesh split,
microbatching, optimizer dtype, chunk sizes) and reports the roofline-term
deltas vs the baseline — the hypothesis -> change -> measure loop, with
each variant's numbers appended to a JSON log.

    PYTHONPATH=src python -m benchmarks.hillclimb \
        --arch qwen3-moe-30b-a3b --shape train_4k \
        --variants baseline,no_fsdp,mb4 --out hillclimb_qwen3moe.json

NOTE: must run in its own process (``main`` sets XLA_FLAGS for 512 host
devices through ``repro.launch.dryrun.force_host_devices``).
"""
from __future__ import annotations

import argparse
import json
import os
import time

from repro.launch.compile_cache import enable_compile_cache
from repro.launch.dryrun import force_host_devices, lower_cell
from repro.train.train_loop import TrainConfig

#: named variants: kwargs for lower_cell
VARIANTS = {
    "baseline": {},
    # --- sharding / mesh ---------------------------------------------------
    "dp64xtp4": {"mesh_shape": (64, 4)},
    "dp32xtp8": {"mesh_shape": (32, 8)},
    "dp128xtp2": {"mesh_shape": (128, 2)},
    "dp256xtp1": {"mesh_shape": (256, 1)},
    "no_fsdp": {"rule_overrides": {"embed": None}},
    # serving: weights resident (no FSDP gather-per-step); MoE experts
    # sharded over the data axis too (EP) so 400B-class params fit
    "serve_weights": {"rule_overrides": {"embed": None}},
    "serve_ep_data": {"rule_overrides": {"embed": None,
                                         "experts": ("data",)}},
    "serve_ep_2d": {"rule_overrides": {"embed": None,
                                       "experts": ("data", "model"),
                                       "mlp": None}},
    # high-TP serving meshes (weights resident at 400B scale)
    "serve_tp64": {"mesh_shape": (4, 64), "rule_overrides": {"embed": None}},
    "serve_tp128": {"mesh_shape": (2, 128),
                    "rule_overrides": {"embed": None}},
    "seq_shard": {"rule_overrides": {"seq": ("model",)}},
    # --- training config ----------------------------------------------------
    "mb4": {"tcfg_override": TrainConfig(microbatches=4)},
    "mb8": {"tcfg_override": TrainConfig(microbatches=8)},
    "bf16_moments": {"tcfg_override": TrainConfig(moment_dtype="bfloat16")},
    "adafactor": {"tcfg_override": TrainConfig(optimizer="adafactor")},
    "no_remat": {"tcfg_override": TrainConfig(remat=False)},
    # --- kernel/chunk geometry ----------------------------------------------
    "q1024": {"settings_extra": {"q_chunk": 1024, "kv_chunk": 1024}},
    "q256": {"settings_extra": {"q_chunk": 256, "kv_chunk": 256}},
    # fused head+cross-entropy: never materialise (B,T,V) f32 logits
    "fused_loss": {"settings_extra": {"vocab_chunk": 16384}},
    "dp256_fused": {"mesh_shape": (256, 1),
                    "settings_extra": {"vocab_chunk": 16384}},
}


def run_variant(arch: str, shape: str, name: str) -> dict:
    kw = dict(VARIANTS[name])
    t0 = time.time()
    cell = lower_cell(arch, shape, multi_pod=False, analyze=True,
                      quiet=True, **kw)
    cell["variant"] = name
    cell["wall_s"] = round(time.time() - t0, 1)
    return cell


def main() -> None:
    force_host_devices()
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--variants", default="baseline")
    ap.add_argument("--out", default="hillclimb.json")
    args = ap.parse_args()

    log = {"arch": args.arch, "shape": args.shape, "runs": []}
    if os.path.exists(args.out):
        with open(args.out) as f:
            log = json.load(f)
    done = {r["variant"] for r in log["runs"] if r.get("ok")}

    base = None
    for r in log["runs"]:
        if r.get("variant") == "baseline" and r.get("ok"):
            base = r
    for name in args.variants.split(","):
        if name in done:
            print(f"[skip] {name} already done")
            continue
        print(f"[run] {args.arch} x {args.shape} x {name}", flush=True)
        try:
            cell = run_variant(args.arch, args.shape, name)
        except Exception as e:
            import traceback
            traceback.print_exc()
            cell = {"variant": name, "ok": False,
                    "error": f"{type(e).__name__}: {e}"}
        log["runs"].append(cell)
        with open(args.out, "w") as f:
            json.dump(log, f, indent=1)
        if cell.get("ok"):
            r = cell["roofline"]
            line = (f"  {name:14s} comp={r['compute_s']:.4f}s "
                    f"mem={r['memory_s']:.4f}s coll={r['collective_s']:.4f}s"
                    f" dom={r['dominant']} step={r['step_s']:.4f}s")
            if base is not None and base is not cell:
                b = base["roofline"]
                line += f"  (step x{r['step_s']/b['step_s']:.3f} vs baseline)"
            print(line, flush=True)
        if cell.get("variant") == "baseline" and cell.get("ok"):
            base = cell


if __name__ == "__main__":
    main()
