"""Serve a model with batched requests through the engine.

    PYTHONPATH=src python examples/serve_lm.py --arch qwen3-1.7b --requests 6
    PYTHONPATH=src python examples/serve_lm.py --reduced --requests 2

The model serves at its published width, with the weights in its compute
dtype; ``--reduced`` swaps in the same-family shrunken config for a quick
run on a CPU.

With ``--report dryrun_single.json`` the decode fleet's mesh is first
planned through the selection service (class A, state-resident), and the
engine records the placement decision.
"""
import argparse
import json
import os

import jax
import jax.numpy as jnp

import repro.configs as configs
from repro.core.costmodel import TpuPriceModel
from repro.core.tpu_flora import service_from_dryrun_report
from repro.launch.compile_cache import enable_compile_cache
from repro.models import build_model, count_params
from repro.serve.engine import (Engine, Request, plan_decode_placement,
                                serving_params)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-1.7b")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--slots", type=int, default=2)
    ap.add_argument("--prompt-len", type=int, default=12)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--reduced", action="store_true",
                    help="serve the shrunken same-family config")
    ap.add_argument("--report", default=None,
                    help="dry-run report: plan the decode mesh via the "
                         "selection service before serving")
    ap.add_argument("--market", default="ondemand",
                    choices=["ondemand", "spot"])
    args = ap.parse_args()
    enable_compile_cache()

    placement = None
    if args.report and os.path.exists(args.report):
        with open(args.report) as f:
            service = service_from_dryrun_report(
                json.load(f), TpuPriceModel(args.market))
        placement = plan_decode_placement(service)
        print(f"[serve] placement: mesh {placement.config_id} "
              f"at {placement.hourly_cost:.2f} $/h "
              f"(class {placement.job_class.value})")

    cfg = configs.get(args.arch)
    if args.reduced:
        cfg = configs.reduced(cfg)
    model = build_model(cfg)
    params = serving_params(model)
    print(f"[serve] {cfg.name}{' (reduced)' if args.reduced else ''}: "
          f"{count_params(model.param_specs())/1e6:.1f}M params, "
          f"{args.slots} decode slots")

    eng = Engine(model, params, slots=args.slots, max_len=64,
                 placement=placement)
    key = jax.random.PRNGKey(1)
    reqs = []
    for i in range(args.requests):
        key, sub = jax.random.split(key)
        prompt = jax.random.randint(sub, (args.prompt_len,), 0,
                                    cfg.vocab_size, dtype=jnp.int32)
        reqs.append(Request(uid=i, prompt=prompt, max_new_tokens=args.max_new))
    comps = eng.serve(reqs)
    for c in sorted(comps, key=lambda c: c.uid):
        print(f"  req {c.uid}: {len(c.tokens)} tokens "
              f"(prefill {c.prefill_ms:.0f} ms, decode {c.decode_ms:.0f} ms) "
              f"-> {c.tokens[:8]}")


if __name__ == "__main__":
    main()
