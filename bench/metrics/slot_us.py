"""Closed-loop slot time: the window over the slots completed in it (host
clock), slots back to back, each waited for before the next is sent.
Read against the 500 us slot budget at 30 kHz."""


def read(run):
    w = run.window
    if w is None or w.n_timed == 0:
        return None
    return w.seconds / w.n_timed * 1e6
