"""Slot stages on the device and the step's dispatch on the host, from the
profiler trace of a ``--trace 1`` run.

``bench.harness.trace`` reduces a trace to device ops and the benchmark's
own ``bench.*`` spans.  This module reads three more things from the same
``.xplane.pb``:

* **Each device op's stage.**  The program runs every stage of the slot
  under a ``jax.named_scope`` (``arches.tx``, ``arches.channel``,
  ``arches.rx``, ``arches.experts``, ``arches.kpm``, ``arches.decide``),
  which lands in the ``op_name`` of every HLO instruction.  A TPU trace
  names a device op by its instruction (``%fusion.75 = ...``); the profiler
  keeps the compiled module that ran (the ``Hlo Proto`` stat of the
  ``/host:metadata`` plane), whose text maps the instruction to its
  ``op_name``.  An op's stage is the innermost ``arches.<stage>`` component
  of that path, whatever transform wraps it (``vmap(arches.tx)``); a fusion
  whose own ``op_name`` names no stage takes its root instruction's.  An op
  under no stage is ``unscoped`` (copies, the slot index's transfer).
* **The host's dispatch.**  The program wraps each call of the compiled
  closed-loop step in an ``arches.slot.dispatch`` host span.  Inside it, the
  runtime's events say where the call's time goes: ``PJRT_LoadedExecutable_
  Execute`` (the runtime's whole execute call), and within it
  ``AllocateOutputBuffersWithInputReuse`` (one device buffer per output
  leaf).  The rest of the span is the program's own Python and JAX's
  argument and output handling.
* **The clock offset** between the device's events and the host's: a
  device program cannot start before its launch
  (``TpuLoadedExecutable::ExecuteLaunch``) began, nor end after the host
  started reading its completion (``ReadSyncFlag``); the two bound how far
  the device's events sit early on the host's clock.

A program without the scopes or the span (an older tree) reads as nothing:
the functions here return ``None`` and never raise for that.
"""

from __future__ import annotations

import bisect
import dataclasses
import re
import sys

import numpy as np

from bench.harness import trace as trace_mod

STAGES = ("tx", "channel", "rx", "experts", "kpm", "decide")
UNSCOPED = "unscoped"
STAGE_RE = re.compile(
    r"(?<![\w.])arches\.(" + "|".join(STAGES) + r")(?![\w.])")
DISPATCH_SPAN = "arches.slot.dispatch"
EXECUTE = "PJRT_LoadedExecutable_Execute"
ALLOC = "AllocateOutputBuffersWithInputReuse"
LAUNCH = "TpuLoadedExecutable::ExecuteLaunch"
SYNC = "ReadSyncFlag"
MODULES_LINE = "XLA Modules"
METADATA_PLANE = "/host:metadata"
HLO_PROTO_STAT = "Hlo Proto"
HOST_EVENTS = (DISPATCH_SPAN, EXECUTE, ALLOC, LAUNCH, SYNC)


# -- the compiled module, from the trace ---------------------------------------


def _varint(buf, i: int):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of one serialized protobuf message: an
    int for a varint, a memoryview for the other wire types."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire == 1:
            value, i = buf[i:i + 8], i + 8
        elif wire == 5:
            value, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"protobuf wire type {wire}")
        yield field, value


def hlo_protos(data: bytes) -> dict:
    """``{module name: serialized HloModuleProto}`` of every module the
    trace's ``/host:metadata`` plane holds.

    Field numbers are those of ``tsl/profiler/protobuf/xplane.proto``
    (``XSpace.planes`` 1; ``XPlane.name`` 2, ``event_metadata`` 4,
    ``stat_metadata`` 5; ``XEventMetadata.name`` 2, ``stats`` 5;
    ``XStat.metadata_id`` 1, ``bytes_value`` 6) and of xla's ``HloProto``
    (``hlo_module`` 1)."""
    out = {}
    for field, plane in _fields(memoryview(data)):
        if field != 1:
            continue
        name, metas, stat_names = None, [], {}
        for f, v in _fields(plane):
            if f == 2:
                name = bytes(v).decode()
            elif f == 4:
                metas.append(v)
            elif f == 5:
                entry = dict(_fields(v))
                stat = dict(_fields(entry.get(2, b"")))
                stat_names[entry.get(1, 0)] = bytes(stat.get(2, b"")).decode()
        if name != METADATA_PLANE:
            continue
        for entry in metas:
            meta = dict(_fields(entry)).get(2, b"")
            module, proto = None, None
            for f, v in _fields(meta):
                if f == 2:
                    module = bytes(v).decode()
                elif f == 5:
                    stat = dict(_fields(v))
                    if stat_names.get(stat.get(1)) == HLO_PROTO_STAT:
                        proto = dict(_fields(stat.get(6, b""))).get(1)
            if module and proto is not None:
                out[module] = bytes(proto)
    return out


def _ints(value) -> list:
    """A repeated integer field's value: one varint, or a packed run."""
    if isinstance(value, int):
        return [value]
    out, i = [], 0
    while i < len(value):
        x, i = _varint(value, i)
        out.append(x)
    return out


def op_stages(module_proto: bytes) -> dict:
    """``{instruction name: stage or None}`` of one serialized
    ``HloModuleProto``: an instruction's stage is that of its own
    ``op_name``, and a fusion whose own names none takes its root's.

    Field numbers are those of xla's ``hlo.proto``: ``HloModuleProto.
    computations`` 3; ``HloComputationProto.instructions`` 2, ``id`` 5,
    ``root_id`` 6; ``HloInstructionProto.name`` 1, ``opcode`` 2,
    ``metadata`` 7, ``id`` 35, ``called_computation_ids`` 38;
    ``OpMetadata.op_name`` 2."""
    instrs, roots = {}, {}
    for field, comp in _fields(memoryview(module_proto)):
        if field != 3:
            continue
        comp_id = root = None
        for f, v in _fields(comp):
            if f == 5:
                comp_id = v
            elif f == 6:
                root = v
            elif f == 2:
                name, opcode, op_name, iid, called = None, None, "", None, []
                for g, w in _fields(v):
                    if g == 1:
                        name = bytes(w).decode()
                    elif g == 2:
                        opcode = bytes(w).decode()
                    elif g == 7:
                        op_name = bytes(dict(_fields(w)).get(2, b"")).decode()
                    elif g == 35:
                        iid = w
                    elif g == 38:
                        called += _ints(w)
                instrs[iid] = (name, opcode, stage_of(op_name), called)
        roots[comp_id] = root

    def resolve(iid, depth=0):
        name, opcode, stage, called = instrs[iid]
        if (stage is None and opcode == "fusion" and called and depth < 16
                and roots.get(called[0]) in instrs):
            return resolve(roots[called[0]], depth + 1)
        return stage

    return {instrs[i][0]: resolve(i) for i in instrs}


def stage_of(op_name: str) -> str | None:
    """The innermost ``arches.<stage>`` of an ``op_name`` path."""
    found = STAGE_RE.findall(op_name)
    return found[-1] if found else None


# -- the reduction ---------------------------------------------------------------


def head(op: str) -> str:
    """The instruction name of a device op named by its HLO text."""
    return op.split(" = ", 1)[0].lstrip("%")


@dataclasses.dataclass
class SlotTrace:
    """What this module reads of one trace beside the device ops and the
    window that ``bench.harness.trace`` keeps (times in ns, trace clock)."""

    trace: trace_mod.Trace  # device ops clipped to the window, the window
    modules: dict  # chip -> sorted [(start, end, module name)], XLA Modules
    host: dict  # event name (HOST_EVENTS) -> sorted [(start, end)]
    stage_of_op: dict  # module name -> {instruction name: stage or None}

    @property
    def window(self) -> tuple:
        return self.trace.window

    def stage_seconds(self) -> dict | None:
        """Device seconds of the window's ops by stage (``UNSCOPED`` for
        ops under none), summed over chips; ``None`` where no op carries a
        stage.  An op takes its stage from the module whose ``XLA
        Modules`` event encloses it, so two programs whose instructions
        share names do not mix."""
        tot = dict.fromkeys(STAGES + (UNSCOPED,), 0)
        for chip in self.trace.ops:
            mods = self.modules.get(chip, [])
            starts = [a for a, _, _ in mods]
            for name, a, b in self.trace.ops_in_window(chip):
                k = bisect.bisect_right(starts, a) - 1
                module = mods[k][2] if k >= 0 and a < mods[k][1] else None
                stage = self.stage_of_op.get(module, {}).get(head(name))
                tot[stage or UNSCOPED] += b - a
        if all(tot[s] == 0 for s in STAGES):
            return None
        return {k: v * 1e-9 for k, v in tot.items()}

    def dispatches(self) -> list:
        """Per ``arches.slot.dispatch`` span inside the window: its seconds,
        and the seconds of the runtime's execute call and of its output
        allocation inside it."""
        w0, w1 = self.window
        out = []
        for a, b in self.host.get(DISPATCH_SPAN, []):
            if a < w0 or b > w1:
                continue
            out.append((
                (b - a) * 1e-9,
                _inside(self.host.get(EXECUTE, []), a, b) * 1e-9,
                _inside(self.host.get(ALLOC, []), a, b) * 1e-9,
            ))
        return out

    def clock_offset(self, chip: int = 0):
        """Bounds ``(lo, hi)``, in seconds, on how early the device's
        events sit against the host's clock, from every program of the
        trace: at least its launch's start less its device start, at most
        the next sync-flag read's start less its device end.  ``None``
        where launches and programs do not pair one to one."""
        mods = self.modules.get(chip, [])
        launches = self.host.get(LAUNCH, [])
        syncs = self.host.get(SYNC, [])
        if not mods or len(mods) != len(launches):
            return None
        sync_starts = np.asarray([s for s, _ in syncs], np.float64)
        lo, hi = [], []
        for (m0, m1, _), (l0, _) in zip(mods, launches):
            lo.append(l0 - m0)
            k = int(np.searchsorted(sync_starts, l0))
            if k < len(sync_starts):
                hi.append(sync_starts[k] - m1)
        if not hi:
            return None
        return float(max(lo)) * 1e-9, float(min(hi)) * 1e-9


def _inside(events: list, a: float, b: float) -> float:
    """Summed duration of the events that lie within ``[a, b]``."""
    return sum(e1 - e0 for e0, e1 in events if e0 >= a and e1 <= b)


def reduce(data: bytes, tr: trace_mod.Trace) -> SlotTrace:
    """Add to ``tr``, the reduction ``bench.harness.trace`` made of one
    serialized ``XSpace`` (the ``.xplane.pb``'s bytes), what it leaves out:
    the ``XLA Modules`` line, the host events in ``HOST_EVENTS`` and each
    module's instruction stages."""
    from jax.profiler import ProfileData

    modules, host = {}, {}
    for plane in ProfileData.from_serialized_xspace(data).planes:
        m = trace_mod.DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    modules.setdefault(int(m.group(1)), []).extend(
                        (e.start_ns, e.end_ns, e.name) for e in line.events)
        elif plane.name == trace_mod.HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if e.name in HOST_EVENTS:
                        host.setdefault(e.name, []).append(
                            (e.start_ns, e.end_ns))
    return SlotTrace(
        trace=tr, modules={c: sorted(v) for c, v in modules.items()},
        host={k: sorted(v) for k, v in host.items()},
        stage_of_op={name: op_stages(proto)
                     for name, proto in hlo_protos(data).items()})


# -- what the readers call -------------------------------------------------------

def of(run) -> SlotTrace | None:
    """The reduction of a run's trace (``None`` without one), made once per
    run and kept on the run's view; the first call prints the stage split,
    the dispatch split and the clock offset to standard error."""
    tr = run.trace
    if tr is None:
        return None
    if "slot_trace" not in vars(run):
        with open(trace_mod.find_xplane(run.run["trace_dir"]), "rb") as f:
            run.slot_trace = reduce(f.read(), tr)
        report(run.slot_trace, run.window.n_timed)
    return run.slot_trace


def report(st: SlotTrace, n_slots: int) -> None:
    sec = st.stage_seconds()
    if sec is None:
        print("stages: no device op carries an arches stage", file=sys.stderr)
    elif n_slots:
        total = sum(sec.values())
        split = ", ".join(f"{k} {v / n_slots * 1e6:.1f}" for k, v in
                          sec.items())
        print(f"stages: us per slot over {n_slots} slots: {split} (of "
              f"{total / n_slots * 1e6:.1f} summed op time)", file=sys.stderr)
    d = st.dispatches()
    if d:
        span, execute, alloc = (np.median(x) * 1e6 for x in zip(*d))
        print(f"stages: {len(d)} {DISPATCH_SPAN} spans, median us: span "
              f"{span:.1f}, execute {execute:.1f}, output allocation "
              f"{alloc:.1f}; longest span {max(x[0] for x in d) * 1e6:.1f}",
              file=sys.stderr)
    off = st.clock_offset()
    if off is not None:
        print(f"stages: device events sit {off[0] * 1e3:.3f} to "
              f"{off[1] * 1e3:.3f} ms early against the host clock",
              file=sys.stderr)


def stage_us(run, stage: str):
    """Device microseconds per slot of the ops under ``stage``."""
    st = of(run)
    sec = st.stage_seconds() if st is not None else None
    if sec is None or run.window.n_timed == 0:
        return None
    return sec[stage] / run.window.n_timed * 1e6


def unscoped_pct(run):
    """Share of the window's summed device-op time under no stage."""
    st = of(run)
    sec = st.stage_seconds() if st is not None else None
    if sec is None:
        return None
    return 100.0 * sec[UNSCOPED] / sum(sec.values())


def dispatch_us(run, part: str):
    """Median per ``arches.slot.dispatch`` span, in microseconds, of
    ``part``: ``"alloc"``, the runtime's output allocation, or ``"self"``,
    the span outside the runtime's execute call.  ``None`` without such
    spans, or where the runtime shows no such event inside them."""
    st = of(run)
    d = st.dispatches() if st is not None else []
    if not d:
        return None
    span, execute, alloc = (np.asarray(x) for x in zip(*d))
    if part == "alloc":
        return float(np.median(alloc)) * 1e6 if alloc.any() else None
    return float(np.median(span - execute)) * 1e6 if execute.any() else None
