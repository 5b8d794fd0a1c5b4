"""Device time of the ``lmmse_detect`` kernel (the per-RE LMMSE detector of
a two-layer uplink) per slot, from the trace.  A one-layer step calls no
detector kernel: there it reads nothing."""

KERNEL = "lmmse_detect"


def read(run):
    tr = run.trace
    if tr is None:
        return None
    match = tr.kernel(KERNEL)
    if tr.op_count(match) == 0:
        return None
    return tr.op_seconds(match) / run.window.n_timed * 1e6
