"""Median host time from the call of the jitted slot step until it
returns, before the wait for its outputs (host clock, traced run)."""

import numpy as np


def read(run):
    w = run.window
    t = np.asarray(w.dispatch_s[w.first_timed:])
    if t.size == 0:
        return None
    return float(np.median(t)) * 1e6
