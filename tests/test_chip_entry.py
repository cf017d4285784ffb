"""The chip entry points' host-side contract: where the compile cache
goes, and that ``chip_smoke.py`` refuses to report off a TPU."""
import importlib.util
import os
import pathlib
import shutil
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.launch import cache

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture
def cache_config():
    """Restore JAX's cache directory (and cache state) after the test."""
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)
    compilation_cache.reset_cache()


def test_compile_cache_leaves_env_setting_alone(cache_config, monkeypatch,
                                                tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_fixed_path_inside_checkout(cache_config, monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = cache.enable_compile_cache()
    assert first == cache.enable_compile_cache() == str(ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == first


def _load_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_smoke_device_check_refuses_cpu():
    smoke = _load_smoke()
    with pytest.raises(smoke.SmokeFailure, match="needs a TPU"):
        smoke.check_device()


def test_smoke_expert_check_finds_a_misplaced_tile():
    """The expert-gradient check keeps only the expert weights, and names
    the one (layer, expert) slice with a (512, 512) tile in the wrong
    place."""
    smoke = _load_smoke()
    rng = np.random.RandomState(0)
    w_up = rng.randn(2, 4, 1024, 1024).astype(np.float32)
    opt = {"m": {"blocks": [{"moe": {"w_up": w_up,
                                     "gate_w": np.ones((2, 1024, 4))},
                             "mlp": {"w_out": np.ones((1024, 8))}}],
                 "embed": np.ones((8, 1024))}}
    want = smoke.expert_grads(opt)
    assert list(want) == ["['blocks'][0]['moe']['w_up']"]
    got = {k: v.copy() for k, v in want.items()}
    tile = got["['blocks'][0]['moe']['w_up']"][1, 2]
    tile[:512, 512:] = tile[512:, :512]
    err, worst = smoke.worst_expert(got, want)
    assert worst == "['blocks'][0]['moe']['w_up'][1, 2]" and err > 0.25
    assert smoke.worst_expert(want, want)[0] == 0.0


@pytest.mark.parametrize("where", ["checkout", "alone"])
def test_smoke_fails_without_result_off_chip(where, tmp_path):
    """Under the CPU backend, and with no repo beside it, the script exits
    non-zero and never prints a result line."""
    script = ROOT / "chip_smoke.py"
    if where == "alone":
        script = pathlib.Path(shutil.copy(script, tmp_path))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, str(script)], cwd=tmp_path,
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0, proc.stdout
    assert '"ok"' not in proc.stdout
