"""The trace reduction on a hand-written trace with known answers."""

import pytest

from bench.harness import trace as trace_mod

US = 1_000_000  # picoseconds


def _events(evs):
    return "\n".join(f"events {{ metadata_id: {m} offset_ps: {a * US} "
                     f"duration_ps: {d * US} }}" for m, a, d in evs)


def _meta(names):
    return "\n".join(f'event_metadata {{ key: {i} value {{ id: {i} '
                     f'name: "{n}" }} }}' for i, n in enumerate(names, 1))


# device ops (us from 0): mmse_interp [10, 30), fusion [20, 50) overlapping
# it, tree_infer [70, 75), and fusion [95, 120) crossing the window's end.
# host spans: window [0, 100), dispatch [0, 10) and [60, 70), sync
# [10, 60) and [70, 100).
TEXT = f"""
planes {{ id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0
    {_events([(1, 10, 20), (2, 20, 30), (3, 70, 5), (2, 95, 25)])} }}
  lines {{ id: 2 name: "XLA Modules" timestamp_ns: 0
    {_events([(4, 0, 200)])} }}
  {_meta(["%mmse_interp.1 = f32[8] custom-call(f32[8] %p)",
           "%fusion.2 = f32[8] fusion(f32[8] %mmse_interp.1)",
           "%tree_infer = f32[8] custom-call(f32[8] %q)", "jit_step"])} }}
planes {{ id: 2 name: "/host:CPU"
  lines {{ id: 1 name: "python" timestamp_ns: 0
    {_events([(1, 0, 100), (2, 0, 10), (3, 10, 50), (2, 60, 10),
              (3, 70, 30)])} }}
  {_meta(["bench.window", "bench.dispatch", "bench.sync"])} }}
"""


@pytest.fixture(scope="module")
def tr():
    from jax.profiler import ProfileData

    return trace_mod.reduce(ProfileData.from_text_proto(TEXT))


def test_window_and_busy_union(tr):
    assert tr.window == (0.0, 100_000.0)
    assert tr.window_s == pytest.approx(100e-6)
    # [10, 50) + [70, 75) + [95, 100): 40 + 5 + 5 us; the module line is
    # not an op, and the op past the window is clipped
    assert tr.busy_intervals(0).tolist() == [[10e3, 50e3], [70e3, 75e3],
                                              [95e3, 100e3]]
    assert tr.busy_s(0) == pytest.approx(50e-6)
    assert tr.mean_busy_s() == pytest.approx(50e-6)


def test_kernel_time_and_count(tr):
    mmse = tr.kernel("mmse_interp")
    assert tr.op_count(mmse) == 1
    assert tr.op_seconds(mmse) == pytest.approx(20e-6)
    assert tr.op_seconds(lambda n: n.startswith("%fusion")) == (
        pytest.approx(35e-6))
    assert tr.op_count(tr.kernel("tree_infer")) == 1  # not the fusion
    assert [n for n, _ in tr.top_ops(2, width=9)] == ["%fusion.2",
                                                       "%mmse_int"]


def test_idle_gaps_go_to_the_host_span_around_them(tr):
    # idle [0, 10) under dispatch, [50, 70) mid 60 under dispatch,
    # [75, 95) under sync
    gaps = dict(tr.idle_gaps(0))
    assert gaps == {"bench.dispatch": pytest.approx(30e-6),
                    "bench.sync": pytest.approx(20e-6)}
    assert sum(gaps.values()) == pytest.approx(tr.window_s - tr.busy_s(0))
