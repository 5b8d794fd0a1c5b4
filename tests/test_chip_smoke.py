"""Chip entry points fail loudly off the chip and never share one.

* ``chip_smoke.py`` exits non-zero on a CPU-only host, before any work, and
  prints no ``"ok": true`` result line (run as a child pinned to the CPU
  backend, so it never loads the TPU library).
* ``repro.compile_cache`` leaves ``JAX_COMPILATION_CACHE_DIR`` to JAX when
  it is set and otherwise keeps the cache at one fixed path in the
  checkout.
* ``benchmarks.bench_sharded`` starts its forced-host-device child only on
  the CPU backend, pinned there with ``JAX_PLATFORMS=cpu``.
"""

import json
import os
import subprocess
import sys

import jax
import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir))


def test_chip_smoke_refuses_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py")],
        env=env, capture_output=True, text=True, timeout=300, cwd=ROOT,
    )
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    assert "needs a TPU" in proc.stderr


@pytest.fixture
def cache_dir_config():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_defers_to_the_environment(monkeypatch, cache_dir_config):
    from repro.compile_cache import enable_compilation_cache

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert enable_compilation_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_a_fixed_checkout_path(
    monkeypatch, cache_dir_config
):
    from repro.compile_cache import DEFAULT_DIR, enable_compilation_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert DEFAULT_DIR == os.path.join(ROOT, ".jax_cache")
    assert enable_compilation_cache() == DEFAULT_DIR
    assert jax.config.jax_compilation_cache_dir == DEFAULT_DIR
    with open(os.path.join(ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_bench_sharded_child_is_pinned_to_the_cpu(monkeypatch):
    from benchmarks import bench_sharded

    seen = {}

    class Done:
        returncode = 0
        stdout = json.dumps({"n_shards": 8}) + "\n"
        stderr = ""

    def fake_run(cmd, *, env, **kw):
        seen.update(cmd=cmd, env=env)
        return Done()

    monkeypatch.setattr(bench_sharded.subprocess, "run", fake_run)
    assert bench_sharded._forced_host_child(4, 8, 8) == {"n_shards": 8}
    assert seen["env"]["JAX_PLATFORMS"] == "cpu"
    assert "--xla_force_host_platform_device_count=8" in seen["env"]["XLA_FLAGS"]
    assert "--child" in seen["cmd"]
