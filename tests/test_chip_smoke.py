"""chip_smoke.py and the compile-cache helper on the CPU.

The smoke itself refuses any backend but a TPU; its phases are rehearsed
here at the reduced widths (the four-chip phase on four virtual CPU
devices, in a subprocess so this process keeps its single device).
"""

import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import pytest

import chip_smoke
from repro.launch import compile_cache

ROOT = Path(__file__).resolve().parent.parent


def test_smoke_refuses_cpu_backend():
    with pytest.raises(SystemExit, match="needs a TPU"):
        chip_smoke.require_tpu()
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "chip_smoke.py"], cwd=ROOT,
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


def test_smoke_serve_phase_reduced():
    chip_smoke.serve_phase(reduced=True, max_seq=512, max_batch=8)


def test_smoke_moe_layer_phase_reduced():
    chip_smoke.moe_layer_phase(reduced=True)


def test_smoke_four_chip_phase_on_virtual_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = textwrap.dedent("""
        import chip_smoke
        chip_smoke.four_chip_phase(reduced=True)
        print("FOUR OK")
    """)
    out = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, f"STDOUT:{out.stdout}\nSTDERR:{out.stderr}"
    assert "each expert server's weights sit on their own device" \
        in out.stdout
    assert "FOUR OK" in out.stdout


def test_compile_cache_honours_env(monkeypatch, tmp_path):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == tmp_path
    assert jax.config.jax_compilation_cache_dir == before   # set no other


def test_compile_cache_defaults_to_checkout(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = compile_cache.enable_compile_cache()
        assert path == ROOT / ".jax_cache"
        assert compile_cache.cache_dir() == path      # fixed, not per run
        assert jax.config.jax_compilation_cache_dir == str(path)
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
