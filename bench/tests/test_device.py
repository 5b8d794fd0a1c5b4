"""The benchmark refuses devices it has no peaks for, and the CPU."""

import json
import os
import subprocess
import sys

import pytest

from bench import run as brun
from bench.tests.tiny import REPO


def test_peaks_known_device():
    p = brun.peaks_for(REPO, "TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9


def test_peaks_refuse_unknown_device_kind():
    with pytest.raises(KeyError, match="no peaks for device kind"):
        brun.peaks_for(REPO, "TPU v9 imaginary")


def test_harness_exits_nonzero_on_cpu_with_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cells = json.load(open(os.path.join(REPO, "BENCHMARK.json")))["workloads"]
    proc = subprocess.run(
        [sys.executable, "-m", "bench.run", "--workload", cells[0]["name"],
         "--seed", "2147483659", "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == brun.EXIT_NO_CHIP
    assert proc.stdout.strip() == ""
    assert "needs a TPU" in proc.stderr
