import argparse
import dataclasses
import json
import math
import os
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

import repro.configs as configs
from repro.configs import shapes as shapes_lib
from repro.launch import mesh as mesh_lib
from repro.launch import roofline as roof_lib
from repro.models import build_model, count_params
from repro.models import settings as settings_lib
from repro.models.types import param_shapes
from repro.sharding import rules as rules_lib
from repro.sharding import ctx as ctx_lib
from repro.train.train_loop import TrainConfig, make_train_step

def force_host_devices() -> None:
    """Give the CPU backend the 512 devices the dry-run meshes span.  XLA
    reads the flag when the backend starts, so call this before anything
    touches a device, from the entry point, in a process of its own."""
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"


# per-arch training memory policy: bf16 moments for the 400B-class config
TRAIN_CFGS: Dict[str, TrainConfig] = {
    "llama4-maverick-400b-a17b": TrainConfig(moment_dtype="bfloat16"),
}
DEFAULT_TRAIN_CFG = TrainConfig()


def _scalar_shardings(tree, mesh):
    return jax.tree_util.tree_map(lambda _: NamedSharding(mesh, P()), tree)


def _active_params(cfg) -> float:
    """Active parameters per token (MoE: routed experts only)."""
    model = build_model(cfg)
    total = count_params(model.param_specs())
    if not cfg.num_experts:
        return float(total)
    f = cfg.moe_d_ff if cfg.moe_d_ff is not None else cfg.d_ff
    per_expert = 3 * cfg.d_model * f
    n_moe_layers = sum(cfg.is_moe_layer(i) for i in range(cfg.num_layers))
    inactive = n_moe_layers * (cfg.num_experts - cfg.experts_per_token) \
        * per_expert
    return float(total - inactive)


def _cycle_info(cfg):
    period = cfg.moe_period if cfg.num_experts else 1
    cyc = math.lcm(len(cfg.block_pattern), period)
    n_cycles, rem = divmod(cfg.num_layers, cyc)
    return cyc, n_cycles, rem


def _depth_variant(cfg, n_cycles_target: int):
    """Same config with only n_cycles_target layer cycles (+ remainder)."""
    cyc, _, rem = _cycle_info(cfg)
    changes = {"num_layers": n_cycles_target * cyc + rem}
    if cfg.encoder_layers:
        enc_cyc, enc_n, enc_rem = 1, cfg.encoder_layers, 0
        changes["encoder_layers"] = n_cycles_target * enc_cyc + enc_rem
    return dataclasses.replace(cfg, **changes)


def build_lowered(cfg, shape, mesh, rules, tcfg, *, settings_kwargs):
    """Lower one cell (no compile)."""
    model = build_model(cfg)
    p_specs = model.param_specs()
    p_sds = param_shapes(p_specs)
    p_sh = rules_lib.tree_shardings(p_specs, rules, mesh)

    if shape.kind == "train":
        step_fn, opt = make_train_step(model, tcfg)
        o_specs = opt.state_specs(p_specs)
        o_sds = param_shapes(o_specs)
        o_sh = rules_lib.tree_shardings(o_specs, rules, mesh)
        b_sds = shapes_lib.batch_specs(cfg, shape, with_labels=True)
        b_sh = rules_lib.batch_shardings(b_sds, rules, mesh)
        m_sds = jax.eval_shape(step_fn, p_sds, o_sds, b_sds)[2]
        jitted = jax.jit(step_fn,
                         in_shardings=(p_sh, o_sh, b_sh),
                         out_shardings=(p_sh, o_sh,
                                        _scalar_shardings(m_sds, mesh)),
                         donate_argnums=(0, 1))
        with mesh, ctx_lib.use(rules, mesh), settings_lib.use(**settings_kwargs):
            return jitted.lower(p_sds, o_sds, b_sds)
    if shape.kind == "prefill":
        b_sds = shapes_lib.batch_specs(cfg, shape, with_labels=False)
        b_sh = rules_lib.batch_shardings(b_sds, rules, mesh)
        if cfg.is_encdec:
            n_text = b_sds["tokens"].shape[1]
            enc_len = b_sds["frontend_embeds"].shape[1]
            s_specs = model.state_specs(shape.global_batch, n_text, enc_len)
        else:
            s_specs = model.state_specs(shape.global_batch, shape.seq_len)
        s_sds = param_shapes(s_specs)
        s_sh = rules_lib.tree_shardings(s_specs, rules, mesh)

        def prefill_fn(params, batch, state):
            return model.prefill(params, batch, state)

        logits_sh = NamedSharding(mesh, rules_lib.spec_for(
            (shape.global_batch, cfg.vocab_size), ("batch", "vocab"),
            rules, mesh))
        jitted = jax.jit(prefill_fn,
                         in_shardings=(p_sh, b_sh, s_sh),
                         out_shardings=(logits_sh, s_sh),
                         donate_argnums=(2,))
        with mesh, ctx_lib.use(rules, mesh), settings_lib.use(**settings_kwargs):
            return jitted.lower(p_sds, b_sds, s_sds)
    # decode
    if cfg.is_encdec:
        s_specs = model.state_specs(shape.global_batch, shape.seq_len,
                                    cfg.frontend_len)
    else:
        s_specs = model.state_specs(shape.global_batch, shape.seq_len)
    s_sds = param_shapes(s_specs)
    s_sh = rules_lib.tree_shardings(s_specs, rules, mesh)
    d_sds = shapes_lib.decode_specs(cfg, shape)
    tok_sh = rules_lib.batch_shardings(
        {"token": d_sds["token"]}, rules, mesh)["token"]

    def serve_step(params, token, pos, state):
        return model.decode_step(params, token, pos, state)

    logits_sh = NamedSharding(mesh, rules_lib.spec_for(
        (shape.global_batch, cfg.vocab_size), ("batch", "vocab"),
        rules, mesh))
    jitted = jax.jit(serve_step,
                     in_shardings=(p_sh, tok_sh, NamedSharding(mesh, P()),
                                   s_sh),
                     out_shardings=(logits_sh, s_sh),
                     donate_argnums=(3,))
    with mesh, ctx_lib.use(rules, mesh), settings_lib.use(**settings_kwargs):
        return jitted.lower(p_sds, d_sds["token"], d_sds["pos"], s_sds)


def _extrapolate(a: roof_lib.Roofline, b: roof_lib.Roofline,
                 n_cycles: int) -> roof_lib.Roofline:
    """total(n) = A + (n-1) * (B - A): A = 1-cycle module, B = 2-cycle."""
    k = n_cycles - 1
    coll = {key: int(a.collectives.get(key, 0)
                     + k * (b.collectives.get(key, 0)
                            - a.collectives.get(key, 0)))
            for key in set(a.collectives) | set(b.collectives)}
    return roof_lib.Roofline(
        flops=a.flops + k * (b.flops - a.flops),
        hbm_bytes=a.hbm_bytes + k * (b.hbm_bytes - a.hbm_bytes),
        wire_bytes=a.wire_bytes + k * (b.wire_bytes - a.wire_bytes),
        collectives=coll)


def lower_cell(arch: str, shape_name: str, *, multi_pod: bool,
               analyze: Optional[bool] = None,
               rule_overrides: Optional[Dict[str, Any]] = None,
               tcfg_override: Optional[TrainConfig] = None,
               mesh_shape: Optional[tuple] = None,
               settings_extra: Optional[Dict[str, Any]] = None,
               quiet: bool = False) -> Dict[str, Any]:
    """Compile one (arch x shape x mesh) cell and report.

    The TRUE config is compiled with rolled loops (this is the deployment
    artifact: memory_analysis + compile proof).  XLA's HloCostAnalysis
    counts while bodies once, so FLOPs/bytes/collectives come from two
    cheap depth-reduced compiles (1 and 2 cycles, attention python-
    unrolled) extrapolated affinely to the real depth.
    """
    cfg = configs.get(arch)
    shape = shapes_lib.SHAPES[shape_name]
    mesh_name = "2x16x16" if multi_pod else "16x16"
    cell: Dict[str, Any] = {"arch": arch, "shape": shape_name,
                            "mesh": mesh_name, "ok": False}
    reason = shapes_lib.skip_reason(cfg, shape)
    if reason:
        cell["skipped"] = reason
        return cell
    if analyze is None:
        analyze = not multi_pod   # roofline table is single-pod (§Roofline)

    if mesh_shape is not None:
        mesh = mesh_lib.make_mesh(tuple(mesh_shape), ("data", "model"))
        cell["mesh"] = mesh_name = \
            f"dp{mesh_shape[0]}xtp{mesh_shape[1]}"
    else:
        mesh = mesh_lib.make_production_mesh(multi_pod=multi_pod)
    rules = rules_lib.production_rules(multi_pod=multi_pod)
    tp = mesh.shape["model"]
    rules = rules.with_overrides(
        **rules_lib.arch_overrides(cfg, tp, kind=shape.kind))
    if rule_overrides:
        rules = rules.with_overrides(**rule_overrides)
    tcfg = tcfg_override or TRAIN_CFGS.get(arch, DEFAULT_TRAIN_CFG)

    # --- 1. true-config compile: the deployment proof -----------------------
    t0 = time.time()
    lowered = build_lowered(cfg, shape, mesh, rules, tcfg,
                            settings_kwargs=dict(settings_extra or {}))
    cell["lower_s"] = round(time.time() - t0, 1)
    t1 = time.time()
    compiled = lowered.compile()
    cell["compile_s"] = round(time.time() - t1, 1)
    mem = roof_lib.memory_analysis_dict(compiled)
    if mem:
        cell["memory"] = mem
        if not quiet:
            print(f"memory_analysis[{arch}/{shape_name}/{mesh_name}]: "
                  f"{json.dumps(mem)}", flush=True)

    cell["params_total"] = count_params(build_model(cfg).param_specs())
    cell["params_active"] = _active_params(cfg)

    # --- 2. cost analysis via depth-reduced pair ------------------------------
    if analyze:
        _, n_cycles, _ = _cycle_info(cfg)
        an_kwargs = dict(unroll_attn=True)
        if shape.kind == "prefill":
            an_kwargs.update(q_chunk=2048, kv_chunk=2048)
        an_kwargs.update(settings_extra or {})
        la = build_lowered(_depth_variant(cfg, 1), shape, mesh, rules, tcfg,
                           settings_kwargs=dict(an_kwargs, layer_unroll=1))
        ra = roof_lib.analyze(la.compile())
        lb = build_lowered(_depth_variant(cfg, 2), shape, mesh, rules, tcfg,
                           settings_kwargs=dict(an_kwargs, layer_unroll=2))
        rb = roof_lib.analyze(lb.compile())
        roof = _extrapolate(ra, rb, n_cycles)
        cell["roofline"] = roof.as_dict()
        n_active = _active_params(cfg)
        model_fl = roof_lib.model_flops_per_step(
            n_active, shape.tokens_per_step, training=(shape.kind == "train"))
        chips = 512 if multi_pod else 256
        cell["model_flops"] = model_fl
        cell["model_flops_per_device"] = model_fl / chips
        cell["useful_flops_ratio"] = \
            (model_fl / chips) / roof.flops if roof.flops else None
        if not quiet:
            print(f"cost_analysis[{arch}/{shape_name}/{mesh_name}]: "
                  f"flops/dev={roof.flops:.3e} bytes/dev={roof.hbm_bytes:.3e}"
                  f" wire/dev={roof.wire_bytes:.3e} dominant={roof.dominant}",
                  flush=True)
    cell["ok"] = True
    return cell


def main() -> None:
    force_host_devices()
    ap = argparse.ArgumentParser(description="multi-pod dry-run")
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--out", default="dryrun_report.json")
    ap.add_argument("--append", action="store_true",
                    help="merge results into an existing report")
    args = ap.parse_args()

    archs = configs.ARCH_NAMES if args.arch == "all" else args.arch.split(",")
    shape_names = list(shapes_lib.SHAPES) if args.shape == "all" \
        else args.shape.split(",")
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    report = {"cells": []}
    if args.append and os.path.exists(args.out):
        with open(args.out) as f:
            report = json.load(f)
    done = {(c["arch"], c["shape"], c["mesh"]) for c in report["cells"]
            if c.get("ok") or c.get("skipped")}

    for multi in meshes:
        mesh_name = "2x16x16" if multi else "16x16"
        for arch in archs:
            for shape_name in shape_names:
                key = (arch, shape_name, mesh_name)
                if key in done:
                    continue
                print(f"=== {arch} x {shape_name} x {mesh_name}", flush=True)
                try:
                    cell = lower_cell(arch, shape_name, multi_pod=multi)
                except Exception as e:
                    traceback.print_exc()
                    cell = {"arch": arch, "shape": shape_name,
                            "mesh": mesh_name, "ok": False,
                            "error": f"{type(e).__name__}: {e}"}
                report["cells"].append(cell)
                with open(args.out, "w") as f:
                    json.dump(report, f, indent=1)
    ok = sum(1 for c in report["cells"] if c.get("ok"))
    skip = sum(1 for c in report["cells"] if c.get("skipped"))
    err = sum(1 for c in report["cells"]
              if not c.get("ok") and not c.get("skipped"))
    print(f"dry-run complete: {ok} ok, {skip} skipped, {err} failed")
    if err:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
