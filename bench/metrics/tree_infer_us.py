"""Device time of the ``tree_infer`` kernel (the decision inference) per
slot, from the trace."""

KERNEL = "tree_infer"


def read(run):
    tr = run.trace
    if tr is None:
        return None
    match = tr.kernel(KERNEL)
    if tr.op_count(match) == 0:
        return None
    return tr.op_seconds(match) / run.window.n_timed * 1e6
