"""The trace reduction on a short trace recorded on a TPU v5e.

``data/x5g_slot_short.xplane.pb`` is a ``--trace 1`` run of
``x5g.slot.gpg`` with a 0.05 s window: two slots, cut to what the
reduction reads (the ``/host:metadata`` plane, which holds the compiled
module, and each device op's metadata stats are taken out).  The reduction's
busy time is checked against a union computed another way (a 100 ns grid),
and its kernel counts against the slots of the window."""

import os

import numpy as np
import pytest

from bench.harness import trace as trace_mod

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "x5g_slot_short.xplane.pb")


@pytest.fixture(scope="module")
def tr():
    return trace_mod.load(DATA)


def test_planes_and_window(tr):
    assert list(tr.ops) == [0]  # one chip
    assert tr.window_s > 0
    names = {s[0] for s in tr.spans}
    assert {"bench.window", "bench.dispatch", "bench.sync"} <= names


def test_busy_is_the_union_of_op_intervals(tr):
    w0, w1 = tr.window
    step = 100.0  # ns
    grid = np.zeros(int(np.ceil((w1 - w0) / step)) + 1, bool)
    for _, a, b in tr.ops_in_window(0):
        grid[int((a - w0) // step): int(np.ceil((b - w0) / step))] = True
    by_grid = grid.sum() * step * 1e-9
    busy = tr.busy_s(0)
    assert 0 < busy <= tr.window_s
    assert abs(by_grid - busy) <= 2 * step * 1e-9 * len(tr.busy_intervals(0))
    summed = sum(b - a for _, a, b in tr.ops_in_window(0)) * 1e-9
    assert busy <= summed + 1e-12


def test_kernels_run_once_per_slot(tr):
    slots = sum(1 for s in tr.spans if s[0] == "bench.dispatch")
    assert slots > 0
    for kernel in ("mmse_interp", "tree_infer"):
        n = tr.op_count(tr.kernel(kernel))
        assert n == slots, (kernel, n, slots)
        assert tr.op_seconds(tr.kernel(kernel)) > 0


def test_idle_gaps_cover_the_idle_time(tr):
    gaps = tr.idle_gaps(0, k=100)
    idle = tr.window_s - tr.busy_s(0)
    assert abs(sum(s for _, s in gaps) - idle) < 1e-9
    assert all(n.startswith(("bench.", "host.")) for n, _ in gaps)
