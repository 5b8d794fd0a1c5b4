"""The stage, dispatch and clock-offset reduction (``bench.harness.stages``)
and its nine readers, on two traces recorded on a TPU v5e.

``data/x5g_slot_scoped.xplane.pb`` is a ``--trace 1`` run of
``x5g.slot.gpg`` with a 0.1 s window: three slots of a program with the
stage scopes and the ``arches.slot.dispatch`` span.  It is cut to what the
reductions read: the device's op and module lines, the host events named in
``stages.HOST_EVENTS`` and ``bench.*``, and the compiled module with only the
instructions the trace names (and each fusion's root), each with its name,
opcode, ``op_name``, id and called computations.
``data/x5g_slot_short.xplane.pb`` is the older recording, of a program with
neither: every new reader reads nothing there, and the readers that were
there read what they read before.
"""

import json
import os
import shutil
import types

import pytest

from bench import run as brun
from bench.harness import result, stages
from bench.harness import trace as trace_mod

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
SCOPED = os.path.join(DATA, "x5g_slot_scoped.xplane.pb")
OLD = os.path.join(DATA, "x5g_slot_short.xplane.pb")
NEW_READERS = ("stage_us.tx", "stage_us.channel", "stage_us.rx",
               "stage_us.experts", "stage_us.kpm", "stage_us.decide",
               "stage_unscoped_pct.slot", "dispatch_alloc_us.slot",
               "dispatch_self_us.slot")
# the trace readers' values on the older recording, read with the readers
# and the reduction as they were before the stage readers came
OLD_VALUES = {"device_idle_pct.slot": 34.88590652084969,
              "mmse_interp_roofline": 18.4635178266571,
              "tree_infer_us": 0.23650000000000002}


def view_of(path: str, tmp_path) -> result.RunView:
    """A ``RunView`` of a recorded trace, as ``bench.run`` builds it."""
    prof = tmp_path / "plugins" / "profile" / "t"
    prof.mkdir(parents=True)
    shutil.copy(path, prof / "t.xplane.pb")
    slots = sum(1 for s in trace_mod.load(path).spans
                if s[0] == "bench.dispatch")
    ctx = types.SimpleNamespace(
        config=json.load(open(os.path.join(
            REPO, "bench", "configs", "x5g_106prb.json"))),
        traffic={}, chips=1,
        peaks=json.load(open(os.path.join(
            REPO, "bench", "peaks.json")))["devices"]["TPU v5 lite"],
    )
    window = types.SimpleNamespace(n_timed=slots, first_timed=0)
    return result.RunView(ctx, {"trace_dir": str(tmp_path),
                                "window": window}, REPO)


def read_all(view, names) -> dict:
    return {n: brun.load_reader(REPO, n).read(view) for n in names}


@pytest.fixture(scope="module")
def scoped():
    with open(SCOPED, "rb") as f:
        return stages.reduce(f.read(), trace_mod.load(SCOPED))


def test_every_op_of_the_window_goes_to_one_stage(scoped):
    sec = scoped.stage_seconds()
    total = scoped.trace.op_seconds(lambda n: True)
    assert sum(sec.values()) == pytest.approx(total, rel=1e-12)
    assert all(sec[s] > 0 for s in stages.STAGES)
    assert sec[stages.UNSCOPED] / total < 0.01
    # the per-RE table gathers of QAM mapping (TX) and the EVM (KPMs)
    assert (sec["tx"] + sec["kpm"]) / total > 0.9


def test_kernels_fall_under_their_stages(scoped):
    for kernel, stage in (("mmse_interp", "experts"),
                          ("tree_infer", "decide")):
        got = {st for ops in scoped.stage_of_op.values()
               for n, st in ops.items() if trace_mod.Trace.kernel(kernel)(n)}
        assert got == {stage}, kernel


def test_dispatch_spans_hold_the_runtime_call(scoped):
    d = scoped.dispatches()
    assert len(d) == 3
    for span, execute, alloc in d:
        assert 0 < alloc < execute < span
        assert alloc / span > 0.6  # the output buffers take most of it


def test_clock_offset_bounds(scoped):
    lo, hi = scoped.clock_offset()
    assert 1.0e-3 < lo < hi < 1.7e-3
    with open(OLD, "rb") as f:
        lo, hi = stages.reduce(f.read(), trace_mod.load(OLD)).clock_offset()
    assert lo == pytest.approx(1.109415e-3)
    assert hi == pytest.approx(1.750129e-3)


def test_new_readers_on_the_scoped_trace(tmp_path, capsys):
    got = read_all(view_of(SCOPED, tmp_path), NEW_READERS)
    assert all(isinstance(v, float) for v in got.values()), got
    assert got["stage_us.tx"] + got["stage_us.kpm"] == pytest.approx(
        42653.6, abs=0.1)
    assert got["stage_us.experts"] == pytest.approx(1599.30, abs=0.01)
    assert got["stage_unscoped_pct.slot"] == pytest.approx(0.2604, abs=1e-4)
    assert got["dispatch_alloc_us.slot"] == pytest.approx(2083.17)
    assert got["dispatch_self_us.slot"] == pytest.approx(500.40)
    err = capsys.readouterr().err
    assert "early against the host clock" in err
    assert "arches.slot.dispatch spans" in err


def test_readers_on_the_older_trace(tmp_path):
    """A program without the scopes and the span: the new readers read
    nothing and raise nothing; the others read what they read before."""
    got = read_all(view_of(OLD, tmp_path), NEW_READERS + tuple(OLD_VALUES))
    assert all(got[n] is None for n in NEW_READERS), got
    for name, value in OLD_VALUES.items():
        assert got[name] == value, name


def test_existing_trace_readers_still_read_the_scoped_trace(tmp_path):
    got = read_all(view_of(SCOPED, tmp_path), tuple(OLD_VALUES))
    assert got["mmse_interp_roofline"] == pytest.approx(
        OLD_VALUES["mmse_interp_roofline"], rel=1e-3)
    assert got["tree_infer_us"] == pytest.approx(
        OLD_VALUES["tree_infer_us"], rel=0.01)
    assert 0 < got["device_idle_pct.slot"] < 100
