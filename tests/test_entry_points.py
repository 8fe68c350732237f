"""The entry points that run on the chip: ``repro.launch.serve``, the
compile-cache helper, and ``chip_smoke.py``'s refusal to run on a CPU."""
import os
import subprocess
import sys
from pathlib import Path

import jax

from repro.launch import compile_cache, serve

ROOT = Path(__file__).resolve().parents[1]


def test_chip_smoke_refuses_cpu():
    """With no accelerator the smoke run fails instead of running its
    phases on the CPU, and prints no result line."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    r = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                       env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "no TPU" in r.stderr


def test_compile_cache_honours_env(monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == "/elsewhere"
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = compile_cache.enable_compile_cache()
        assert path == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_serve_backends_decide_identically(monkeypatch):
    """The served path through ``serve.main``: the kernel backend answers
    every request exactly as the numpy reference does."""
    monkeypatch.setattr(serve, "enable_compile_cache", lambda: None)
    argv = ["--smoke", "--requests", "60", "--capacity", "8"]
    dev = serve.main(argv + ["--backend", "kernel"])
    ref = serve.main(argv + ["--backend", "numpy"])
    assert [r.cached for r in dev["done"]] == [r.cached for r in ref["done"]]
    assert ([r.out_tokens for r in dev["done"]]
            == [r.out_tokens for r in ref["done"]])
    assert dev["events"] == ref["events"]
    assert dev["stats"]["hits"] > 0 and dev["stats"]["evictions"] > 0
    assert dev["metrics"]["hook_errors"] == 0
