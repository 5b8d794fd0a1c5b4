"""Capture a profiler trace of the window and reduce it to intervals.

The JAX profiler writes an ``.xplane.pb``; ``jax.profiler.ProfileData``
reads it.  Device operations are the events on the ``XLA Ops`` line of each
``/device:TPU:<n>`` plane; the benchmark's own host spans
(``bench.*`` ``TraceAnnotation``s) are events on the host plane.  Times
are nanoseconds on the trace's common clock.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"


@dataclasses.dataclass
class Trace:
    """Device ops per chip and the benchmark's host spans, in ns."""

    ops: dict  # chip id -> [(name, start_ns, end_ns)] clipped to window
    spans: list  # (name, start_ns, end_ns) host spans named bench.*
    window: tuple  # (start_ns, end_ns) of the bench.window span

    @staticmethod
    def kernel(name: str):
        """A matcher for the ops of one Pallas kernel.  A TPU trace names an
        op by its HLO text, ``%<instruction> = <shape> <opcode>(...)``; a
        Pallas kernel's instruction is ``<kernel name>.<n>``, and other ops
        may name it among their operands."""
        def match(op: str) -> bool:
            head = op.split(" = ", 1)[0].lstrip("%")
            return head == name or head.startswith(name + ".")
        return match

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def ops_in_window(self, chip: int) -> list:
        return self.ops[chip]

    def busy_intervals(self, chip: int) -> np.ndarray:
        """Union of the chip's op intervals inside the window, (k, 2)."""
        iv = sorted((a, b) for _, a, b in self.ops_in_window(chip))
        merged = []
        for a, b in iv:
            if merged and a <= merged[-1][1]:
                merged[-1][1] = max(merged[-1][1], b)
            else:
                merged.append([a, b])
        return np.asarray(merged, np.float64).reshape(-1, 2)

    def busy_s(self, chip: int) -> float:
        iv = self.busy_intervals(chip)
        return float(np.sum(iv[:, 1] - iv[:, 0])) * 1e-9

    def mean_busy_s(self) -> float:
        return float(np.mean([self.busy_s(c) for c in self.ops]))

    def op_seconds(self, match) -> float:
        """Device seconds of the ops whose name ``match`` accepts, summed
        over chips, inside the window."""
        return sum((b - a) for c in self.ops
                   for n, a, b in self.ops_in_window(c) if match(n)) * 1e-9

    def op_count(self, match) -> int:
        return sum(1 for c in self.ops
                   for n, _, _ in self.ops_in_window(c) if match(n))

    def top_ops(self, k: int = 10, width: int = 160) -> list:
        """The ``k`` ops that took most device time, by HLO text (cut to
        ``width`` characters)."""
        tot = {}
        for c in self.ops:
            for n, a, b in self.ops_in_window(c):
                tot[n] = tot.get(n, 0.0) + (b - a) * 1e-9
        return sorted(([n[:width], s] for n, s in tot.items()),
                      key=lambda x: -x[1])[:k]

    def idle_gaps(self, chip: int, k: int = 10) -> list:
        """Idle device time inside the window by what the host was doing:
        each gap between busy intervals goes to the innermost ``bench.*``
        host span that covers its midpoint (``host.other`` where none)."""
        iv = self.busy_intervals(chip)
        w0, w1 = self.window
        edges = np.concatenate([[w0], iv.ravel(), [w1]]).reshape(-1, 2)
        spans = sorted((s for s in self.spans if s[0] != WINDOW_SPAN),
                       key=lambda s: s[1])
        starts = np.asarray([s[1] for s in spans], np.float64)
        tot = {}
        for a, b in edges:
            if b <= a:
                continue
            mid = 0.5 * (a + b)
            name = "host.other"
            i = int(np.searchsorted(starts, mid, side="right")) - 1
            # spans do not overlap except by nesting; look back a few
            for j in range(i, max(i - 4, -1), -1):
                if spans[j][1] <= mid < spans[j][2]:
                    name = spans[j][0]
                    break
            tot[name] = tot.get(name, 0.0) + (b - a) * 1e-9
        return sorted(([n, s] for n, s in tot.items()),
                      key=lambda x: -x[1])[:k]


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(path: str) -> Trace:
    """Reduce one ``.xplane.pb`` to device ops and bench host spans."""
    from jax.profiler import ProfileData

    return reduce(ProfileData.from_file(path), path)


def reduce(pd, what: str = "trace") -> Trace:
    """Reduce a ``jax.profiler.ProfileData``."""
    ops, spans = {}, []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            evs = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    evs.extend((e.name, e.start_ns, e.end_ns)
                               for e in line.events)
            ops[int(m.group(1))] = evs
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                spans.extend((e.name, e.start_ns, e.end_ns)
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    win = [s for s in spans if s[0] == WINDOW_SPAN]
    if not win:
        raise ValueError(f"{what}: no {WINDOW_SPAN} host span")
    if not ops:
        raise ValueError(f"{what}: no /device:TPU plane")
    w0, w1 = win[0][1], win[0][2]
    ops = {c: [(n, max(a, w0), min(b, w1)) for n, a, b in evs
               if b > w0 and a < w1] for c, evs in ops.items()}
    return Trace(ops=ops, spans=spans, window=(w0, w1))


def dump_names(trace: Trace, path: str) -> None:
    """Write every device op name with its count and seconds (for a look
    at a trace by hand)."""
    tot = {}
    for c in trace.ops:
        for n, a, b in trace.ops_in_window(c):
            cnt, s = tot.get(n, (0, 0.0))
            tot[n] = (cnt + 1, s + (b - a) * 1e-9)
    with open(path, "w") as f:
        for n, (cnt, s) in sorted(tot.items(), key=lambda x: -x[1][1]):
            f.write(f"{s:.6f}\t{cnt}\t{n}\n")
