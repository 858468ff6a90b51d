"""Process-level set-up of the entry points: where the persistent compile
cache goes, and benchmark children that must stay off the chip."""

import os
import subprocess

import jax
import pytest

from repro.launch import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_defaults_to_checkout(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.configure_compile_cache()
    assert path == os.path.join(REPO, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path


def test_compile_cache_follows_env(monkeypatch, restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.configure_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before


@pytest.mark.parametrize("suite", ["replica_throughput", "failover"])
def test_benchmark_child_runs_on_cpu_and_fails_loudly(monkeypatch, suite):
    import importlib
    mod = importlib.import_module(f"benchmarks.{suite}")
    seen = {}

    def fake_run(cmd, **kw):
        seen.update(kw["env"])
        return subprocess.CompletedProcess(cmd, 3, stdout="", stderr="boom")

    monkeypatch.setattr(mod.subprocess, "run", fake_run)
    with pytest.raises(RuntimeError, match="boom"):
        mod.run(None)
    assert seen["JAX_PLATFORMS"] == "cpu"
