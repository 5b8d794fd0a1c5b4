"""The stage, dispatch and clock-offset reduction on a hand-written trace
with known answers, and its nine readers on it."""

import os
import types

import pytest

from bench import run as brun
from bench.harness import stages
from bench.harness import trace as trace_mod

US = 1_000_000  # picoseconds
REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))


def _varint(x: int) -> bytes:
    out = bytearray()
    while True:
        low, x = x & 0x7F, x >> 7
        out.append(low | (0x80 if x else 0))
        if not x:
            return bytes(out)


def _field(num: int, value) -> bytes:
    if isinstance(value, int):
        return _varint(num << 3) + _varint(value)
    value = value.encode() if isinstance(value, str) else value
    return _varint(num << 3 | 2) + _varint(len(value)) + value


def _instr(iid, name, opcode, op_name=None, calls=None) -> bytes:
    out = _field(1, name) + _field(2, opcode) + _field(35, iid)
    if op_name is not None:
        out += _field(7, _field(2, op_name))
    if calls is not None:
        out += _field(38, _varint(calls))
    return out


def _computation(cid, name, root, instrs) -> bytes:
    return (_field(1, name) + _field(5, cid) + _field(6, root)
            + b"".join(_field(2, i) for i in instrs))


# the entry computation's device ops, with what decides each one's stage:
#   fusion.1    own op_name names no stage; its root's is under
#               vmap(arches.tx) in a nested jit            -> tx
#   fusion.2    own op_name under vmap(arches.kpm)        -> kpm
#   mmse_interp.3  under arches.experts                     -> experts
#   copy.4      no metadata                                 -> unscoped
#   fusion.5    no metadata; its root's under arches.rx      -> rx
MODULE = (
    _field(1, "jit_step")
    + _field(3, _computation(1, "fused_computation", 11, [
        _instr(10, "param_0", "parameter"),
        _instr(11, "gather.1", "gather",
               "jit(step)/vmap(arches.tx)/jit(modulate)/jit(_take)/gather"),
    ]))
    + _field(3, _computation(2, "fused_computation.1", 21, [
        _instr(21, "multiply.3", "multiply", "jit(step)/arches.rx/mul"),
    ]))
    + _field(3, _computation(3, "main", 5, [
        _instr(1, "fusion.1", "fusion", "jit(step)/vmap(jit(_take))/gather",
               calls=1),
        _instr(2, "fusion.2", "fusion",
               "jit(step)/vmap(arches.kpm)/jit(nearest_point)/add"),
        _instr(3, "mmse_interp.3", "custom-call",
               "jit(step)/arches.experts/jit(mmse_interp)/pallas_call"),
        _instr(4, "copy.4", "copy"),
        _instr(5, "fusion.5", "fusion", calls=2),
    ]))
)
HLO_PROTO = _field(1, MODULE)  # xla's HloProto: hlo_module = 1


def _events(evs):
    return "\n".join(f"events {{ metadata_id: {m} offset_ps: {int(a * US)} "
                     f"duration_ps: {int(d * US)} }}" for m, a, d in evs)


def _meta(names):
    return "\n".join(f'event_metadata {{ key: {i} value {{ id: {i} '
                     f'name: "{n}" }} }}' for i, n in enumerate(names, 1))


def _octal(data: bytes) -> str:
    return "".join(f"\\{b:03o}" for b in data)


# Two slots in a window [0, 200) us.  Device: program [10, 60) and
# [110, 160); per slot fusion.1 20 us, fusion.2 15, mmse_interp.3 5, copy.4
# 2, fusion.5 3.  Host: dispatch span [0, 8) (execute [1, 6), allocation
# [2, 5)) and [100, 190), a spell (execute [101, 104), allocation
# [101.5, 103.5)); launches at 7 and 105, sync-flag reads at 70 and 175.
SLOT_OPS = [(1, 10, 20), (2, 30, 15), (3, 45, 5), (4, 50, 2), (5, 52, 3)]
TEXT = f"""
planes {{ id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0
    {_events(SLOT_OPS + [(m, a + 100, d) for m, a, d in SLOT_OPS])} }}
  lines {{ id: 2 name: "XLA Modules" timestamp_ns: 0
    {_events([(6, 10, 50), (6, 110, 50)])} }}
  {_meta(["%fusion.1 = f32[8] fusion(f32[8] %p), calls=%fused_computation",
          "%fusion.2 = f32[8] fusion(f32[8] %fusion.1)",
          "%mmse_interp.3 = f32[8] custom-call(f32[8] %fusion.2)",
          "%copy.4 = f32[8] copy(f32[8] %mmse_interp.3)",
          "%fusion.5 = f32[8] fusion(f32[8] %copy.4)",
          "jit_step(1)"])} }}
planes {{ id: 2 name: "/host:CPU"
  lines {{ id: 1 name: "python" timestamp_ns: 0
    {_events([(1, 0, 200), (2, 0, 8), (2, 100, 90)])} }}
  lines {{ id: 2 name: "main" timestamp_ns: 0
    {_events([(3, 1, 5), (4, 2, 3), (5, 7, 1), (3, 101, 3), (4, 101.5, 2),
              (5, 105, 1)])} }}
  lines {{ id: 3 name: "sync" timestamp_ns: 0
    {_events([(6, 70, 1), (6, 175, 1)])} }}
  {_meta(["bench.window", "arches.slot.dispatch",
          "PJRT_LoadedExecutable_Execute",
          "AllocateOutputBuffersWithInputReuse",
          "TpuLoadedExecutable::ExecuteLaunch", "ReadSyncFlag"])} }}
planes {{ id: 3 name: "/host:metadata"
  event_metadata {{ key: 1 value {{ id: 1 name: "jit_step(1)"
    stats {{ metadata_id: 1 bytes_value: "{_octal(HLO_PROTO)}" }} }} }}
  stat_metadata {{ key: 1 value {{ id: 1 name: "Hlo Proto" }} }} }}
"""


def _serialize(text: str) -> bytes:
    from jax.profiler import ProfileData

    return ProfileData.text_proto_to_serialized_xspace(text)


def _reduce(data: bytes) -> stages.SlotTrace:
    from jax.profiler import ProfileData

    tr = trace_mod.reduce(ProfileData.from_serialized_xspace(data))
    return stages.reduce(data, tr)


@pytest.fixture(scope="module")
def data():
    return _serialize(TEXT)


def test_module_and_op_stages(data):
    protos = stages.hlo_protos(data)
    assert list(protos) == ["jit_step(1)"]
    got = stages.op_stages(protos["jit_step(1)"])
    assert {k: got[k] for k in ("fusion.1", "fusion.2", "mmse_interp.3",
                                "copy.4", "fusion.5")} == {
        "fusion.1": "tx", "fusion.2": "kpm", "mmse_interp.3": "experts",
        "copy.4": None, "fusion.5": "rx"}


def test_stage_of_takes_the_innermost_stage():
    assert stages.stage_of("jit(f)/vmap(arches.rx)/jit(g)/arches.kpm/add") == (
        "kpm")
    assert stages.stage_of("jit(f)/vmap(arches.tx)/mul") == "tx"
    assert stages.stage_of("jit(f)/arches.slot.dispatch/mul") is None
    assert stages.stage_of("jit(f)/arches.txt/mul") is None


def test_reduction(data):
    st = _reduce(data)
    assert st.window == (0, 200_000)
    sec = st.stage_seconds()
    assert sec == pytest.approx({"tx": 40e-6, "channel": 0.0, "rx": 6e-6,
                                 "experts": 10e-6, "kpm": 30e-6,
                                 "decide": 0.0, "unscoped": 4e-6})
    assert st.dispatches() == [pytest.approx((8e-6, 5e-6, 3e-6)),
                               pytest.approx((90e-6, 3e-6, 2e-6))]
    lo, hi = st.clock_offset()
    assert lo == pytest.approx(-3e-6)  # max(7 - 10, 105 - 110) us
    assert hi == pytest.approx(10e-6)  # min(70 - 60, 175 - 160) us


def test_readers(data, tmp_path):
    prof = tmp_path / "plugins" / "profile" / "t"
    prof.mkdir(parents=True)
    (prof / "t.xplane.pb").write_bytes(data)
    view = types.SimpleNamespace(run={"trace_dir": str(tmp_path)},
                                 window=types.SimpleNamespace(n_timed=2),
                                 trace=_reduce(data).trace)
    got = {n: brun.load_reader(REPO, n).read(view) for n in (
        "stage_us.tx", "stage_us.channel", "stage_us.rx",
        "stage_us.experts", "stage_us.kpm", "stage_us.decide",
        "stage_unscoped_pct.slot", "dispatch_alloc_us.slot",
        "dispatch_self_us.slot")}
    assert got == pytest.approx({
        "stage_us.tx": 20.0, "stage_us.channel": 0.0, "stage_us.rx": 3.0,
        "stage_us.experts": 5.0, "stage_us.kpm": 15.0,
        "stage_us.decide": 0.0, "stage_unscoped_pct.slot": 100 * 4 / 90,
        # medians of two spans: allocation (3, 2); outside the execute
        # call (3, 87), the second a spell
        "dispatch_alloc_us.slot": 2.5, "dispatch_self_us.slot": 45.0,
    })


# A second program whose instruction shares a name with the step's:
# fusion.1 is under arches.decide there.  Device: the step [10, 60) runs
# fusion.1 [10, 30), the other program [110, 160) runs its fusion.1
# [110, 130), and an op at [170, 175) lies in no program.
OTHER = _field(1, _field(1, "jit_other") + _field(3, _computation(
    1, "main", 1, [_instr(1, "fusion.1", "fusion",
                          "jit(other)/arches.decide/add")])))
TWO_PROGRAMS = f"""
planes {{ id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0
    {_events([(1, 10, 20), (1, 110, 20), (1, 170, 5)])} }}
  lines {{ id: 2 name: "XLA Modules" timestamp_ns: 0
    {_events([(2, 10, 50), (3, 110, 50)])} }}
  {_meta(["%fusion.1 = f32[8] fusion(f32[8] %p)", "jit_step(1)",
          "jit_other(2)"])} }}
planes {{ id: 2 name: "/host:CPU"
  lines {{ id: 1 name: "python" timestamp_ns: 0 {_events([(1, 0, 200)])} }}
  {_meta(["bench.window"])} }}
planes {{ id: 3 name: "/host:metadata"
  event_metadata {{ key: 1 value {{ id: 1 name: "jit_step(1)"
    stats {{ metadata_id: 1 bytes_value: "{_octal(HLO_PROTO)}" }} }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "jit_other(2)"
    stats {{ metadata_id: 1 bytes_value: "{_octal(OTHER)}" }} }} }}
  stat_metadata {{ key: 1 value {{ id: 1 name: "Hlo Proto" }} }} }}
"""


def test_ops_take_the_stage_of_their_own_program():
    st = _reduce(_serialize(TWO_PROGRAMS))
    assert set(st.stage_of_op) == {"jit_step(1)", "jit_other(2)"}
    assert st.stage_seconds() == pytest.approx({
        "tx": 20e-6, "channel": 0.0, "rx": 0.0, "experts": 0.0, "kpm": 0.0,
        "decide": 20e-6, "unscoped": 5e-6})
