"""What the metric readers see of a run, and the result line.

A reader (``bench/metrics/<name>.py``) gets a ``RunView`` and returns a
number, or ``None`` where it finds nothing to read in this run; then the
metric is left out of the line.
"""

from __future__ import annotations

import functools
import importlib.util
import os

import numpy as np

from bench.harness import trace as trace_mod

TOP_K = 10


class RunView:
    def __init__(self, ctx, run: dict, root: str):
        self.ctx = ctx
        self.run = run
        self.root = root
        self.config = ctx.config
        self.traffic = ctx.traffic
        self.chips = ctx.chips
        self.peaks = ctx.peaks
        self.window = run.get("window")
        self.outputs = run.get("outputs")

    @functools.cached_property
    def trace(self):
        """The reduced profiler trace of the window (``--trace 1`` runs)."""
        if not self.run.get("trace_dir"):
            return None
        tr = trace_mod.load(trace_mod.find_xplane(self.run["trace_dir"]))
        trace_mod.dump_names(tr, os.path.join(self.run["trace_dir"],
                                              "device_ops.tsv"))
        return tr

    def work(self, kernel: str):
        """``bench/work/<kernel>.py``: the operations and bytes of a kernel
        or expert, computed from shapes."""
        path = os.path.join(self.root, "bench", "work", f"{kernel}.py")
        spec = importlib.util.spec_from_file_location(f"bench_work_{kernel}",
                                                      path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def timed_outputs(self, key: str):
        """``(timed slots, UEs)`` host array of one output leaf."""
        return self.outputs[key][self.window.first_timed:]


def assemble(ctx, run: dict, view: RunView, metrics: dict,
             checks: dict) -> dict:
    """The last line: the contract's keys, then every compared number."""
    snr = view.timed_outputs("kpms/oai/snr")
    rsrp = view.timed_outputs("kpms/aerial/rsrp")
    line = {
        "correct": bool(checks) and all(c["ok"] for c in checks.values()),
        "attempted": int(snr.size),
        "failed": int(np.sum(~(np.isfinite(snr) & np.isfinite(rsrp)))),
        "metrics": metrics,
        "device": dict(ctx.device,
                       memory_peak_bytes=int(run["memory_peak_bytes"])),
    }
    if ctx.trace and view.trace is not None:
        tr = view.trace
        line["device"]["busy_s"] = tr.mean_busy_s()
        line["device"]["window_s"] = tr.window_s
        line["breakdown"] = {
            "device_ops": tr.top_ops(TOP_K),
            "idle_gaps": tr.idle_gaps(min(tr.ops), TOP_K),
        }
    line["checks"] = {k: {"value": c["value"], "limit": c["limit"]}
                      for k, c in checks.items()}
    return line
