"""chip_smoke.py's phases at tiny sizes on the CPU (Pallas interpreted),
and its refusal to run anywhere but on a TPU."""
import dataclasses
import importlib.util
import json
import os
import pathlib

import jax
import pytest

import repro.configs as configs

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_selector_fleet_phase_small(smoke):
    out = smoke.phase_selector_fleet(n_jobs=16, n_cfgs=64, n_members=3,
                                     n_ticks=4, frac=0.05, k=3)
    assert out["heads_within_contract"] == 2 * 4 * 3
    assert out["pallas_program"] == "interpret"


def test_selector_fleet_phase_demands_the_kernel(smoke):
    """On the CPU the fused programs lower to the interpreter, which the
    chip run refuses."""
    out = smoke.phase_selector_fleet(n_jobs=8, n_cfgs=16, n_members=2,
                                     n_ticks=1, k=2)
    with pytest.raises(smoke.SmokeFailure, match="interpreter"):
        smoke.require_kernel(out)


def test_served_path_phase_small(smoke):
    out = smoke.phase_served_path(n_subs=60, n_ticks=6, workers=2)
    assert out["audit"] == "clean"
    assert out["decisions"] + out["rejected"] == 60
    assert out["ticks"] == 6


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_lm_serving_phase_small(smoke, dtype):
    cfg = dataclasses.replace(configs.reduced(configs.get("qwen3-1.7b")),
                              dtype=dtype)
    out = smoke.phase_lm_serving(cfg, n_requests=3, slots=2, prompt_len=8,
                                 max_new=4, max_len=16)
    assert out["decode_vs_forward_rel_l2_max"] <= smoke.LM_PARITY_REL_L2
    assert out["dtype"] == dtype


def test_lm_parity_bound_catches_a_stale_cache(smoke, monkeypatch):
    """A decode step that drops its cache update must fail the parity
    check, not pass under the bfloat16 bound."""
    from repro.models import lm

    real = lm.LM.decode_step

    def stale(self, params, token, pos, state):
        logits, _ = real(self, params, token, pos, state)
        return logits, state

    monkeypatch.setattr(lm.LM, "decode_step", stale)
    cfg = configs.reduced(configs.get("qwen3-1.7b"))
    with pytest.raises(smoke.SmokeFailure):
        smoke.phase_lm_serving(cfg, n_requests=2, slots=2, prompt_len=8,
                               max_new=4, max_len=16)


def test_sharded_fleet_phase_small(smoke):
    n = jax.device_count()
    out = smoke.phase_sharded_fleet(n_devices=n, n_jobs=12, n_cfgs=8 * n,
                                    n_members=3, n_ticks=3, frac=0.2, k=3)
    assert out["collective_dispatches_per_tick"] == 1.0


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]])
def test_main_refuses_without_a_tpu(smoke, capsys, argv):
    assert jax.devices()[0].platform != "tpu"
    assert smoke.main(argv) != 0
    out = capsys.readouterr()
    assert "Nothing ran" in out.err
    assert "PASS" not in out.out
    for line in out.out.splitlines():
        with pytest.raises(ValueError):
            json.loads(line)


def test_compile_cache_defaults_to_the_checkout(monkeypatch):
    from repro.launch import compile_cache

    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        assert compile_cache.enable_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = compile_cache.enable_compile_cache()
        assert path == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert os.path.basename(path) == ".jax_cache"
