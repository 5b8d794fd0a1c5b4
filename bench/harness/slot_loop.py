"""Traffic driver ``slot_loop``: closed-loop slots, back to back.

Reads a traffic file's channel phases, builds the session the cell
declares, and drives the compiled one-slot closed-loop step one slot after
another: each slot is dispatched, then waited for (``block_until_ready``)
before the next is sent.  Each slot's decision is committed to the switch
register for the next slot inside the step, so the loop is closed by the
program itself; nothing paces it.  Slot ``s`` runs the traffic cycle's
conditions for ``s mod cycle_slots`` and folds ``s`` into every UE's key,
so every slot draws fresh channels, noise and payload from the seed.

Set-up (timed as ``setup_s``, from process start to the first timed slot):
the weights, the session, the policy fit, the step's compilation or cache
load, and ``warmup_slots`` slots run through the same step.  The warm-up
slots are the campaign's first slots: their outputs are kept for the
checks, which replay the loop from slot 0.
"""

from __future__ import annotations

import contextlib
import sys
import time

import numpy as np

from bench.harness import program


class Window:
    """Per-slot host times and output references of one run."""

    def __init__(self):
        self.outs = []  # one output pytree (device arrays) per slot
        self.dispatch_s = []  # call to return of the jitted step
        self.slot_s = []  # call to outputs ready
        self.first_timed = 0  # index of the first slot in the window
        self.t0 = self.t1 = 0.0

    @property
    def n_timed(self) -> int:
        return len(self.outs) - self.first_timed

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


def _run_slots(prog, state, window: Window, slot0: int, *, until=None,
               n=None, annotate=False):
    """Run slots from ``slot0`` until ``until`` (perf_counter) or ``n``."""
    import jax

    link, sw = state
    cycle = len(prog.params)
    s = slot0
    span = (jax.profiler.TraceAnnotation if annotate
            else contextlib.nullcontext)
    while True:
        idx = np.int32(s)
        p = prog.params[s % cycle]
        t0 = time.perf_counter()
        with span("bench.dispatch"):
            link, sw, out = prog.step(link, sw, idx, p)
        t1 = time.perf_counter()
        with span("bench.sync"):
            jax.block_until_ready(out)
        t2 = time.perf_counter()
        with span("bench.record"):
            window.outs.append(out)
            window.dispatch_s.append(t1 - t0)
            window.slot_s.append(t2 - t0)
        s += 1
        if n is not None and s - slot0 >= n:
            break
        if until is not None and t2 >= until:
            break
    return (link, sw), s


def run(ctx, host_policies=None) -> dict:
    """One run of a ``slot_loop`` cell; returns what the readers and the
    checks need (see ``bench.run``).  ``host_policies`` reuses a fitted
    policy (``bench.control`` only; a benchmark run fits its own)."""
    import jax

    cfg, traffic = ctx.config, ctx.traffic
    weights = program.make_ai_weights(cfg)
    session = program.build_session(cfg, traffic, weights, host_policies)
    t = time.perf_counter()
    session.host_policies  # the tree's profile-and-fit
    policy_fit_s = time.perf_counter() - t
    prog = program.slot_program(session, ctx.seed)
    window = Window()
    state, s = _run_slots(prog, (prog.link0, prog.sw0), window, 0,
                          n=traffic["warmup_slots"])
    setup_s = time.perf_counter() - ctx.t_start

    compiles = count_compiles()
    trace_dir = None
    if ctx.trace:
        trace_dir = ctx.out_dir("trace")
        jax.profiler.start_trace(trace_dir)
    window.first_timed = len(window.outs)
    with (jax.profiler.TraceAnnotation("bench.window") if ctx.trace
          else contextlib.nullcontext()):
        window.t0 = time.perf_counter()
        state, s = _run_slots(prog, state, window, s,
                              until=window.t0 + ctx.seconds,
                              annotate=ctx.trace)
        window.t1 = time.perf_counter()
    if ctx.trace:
        jax.profiler.stop_trace()
    memory_peak = ctx.memory_peak_bytes()
    in_window = compiles()
    q = lambda t: np.percentile(  # noqa: E731
        np.asarray(t[window.first_timed:]) * 1e3, [0, 10, 50, 90, 100])
    print(f"slot_loop: {window.n_timed} slots in {window.seconds:.3f} s, "
          f"{in_window} compilations in the window, setup {setup_s:.2f} s "
          f"(policy fit {policy_fit_s:.2f} s); slot ms min/p10/p50/p90/max "
          f"{np.round(q(window.slot_s), 3).tolist()}, dispatch ms "
          f"{np.round(q(window.dispatch_s), 3).tolist()}",
          file=sys.stderr, flush=True)

    fetched = fetch_outputs(window.outs)
    del state
    return {
        "session": session, "program": prog, "weights": weights,
        "window": window, "outputs": fetched, "setup_s": setup_s,
        "policy_fit_s": policy_fit_s, "memory_peak_bytes": memory_peak,
        "trace_dir": trace_dir,
    }


def count_compiles():
    """Start counting XLA compilations; the returned function reads the
    count so far."""
    from jax import monitoring

    n = [0]

    def on_event(event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            n[0] += 1

    monitoring.register_event_duration_secs_listener(on_event)
    return lambda: n[0]


def fetch_outputs(outs: list) -> dict:
    """Stack the leaves the checks read into host ``(slots, UEs)`` arrays."""
    import jax

    names = {"/".join(p): p for p in program.OUTPUT_LEAVES}
    leaves = [{k: program.pick(o, p) for k, p in names.items()} | {
        "feat/" + n: v for n, v in program.flat_kpms(o).items()}
        for o in outs]
    host = jax.device_get(leaves)
    return {k: np.stack([h[k] for h in host]) for k in host[0]}
