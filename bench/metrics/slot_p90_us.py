"""90th percentile of the per-slot time: host dispatch of the slot's
compiled step until its outputs are ready, over every slot of the window
(host clock, in the traced run).  The 90th is the highest percentile with
ten slots or more beyond it in a 10 s window of this cell's slot time."""

import numpy as np


def read(run):
    w = run.window
    t = np.asarray(w.slot_s[w.first_timed:])
    if t.size < 100:
        return None
    return float(np.percentile(t, 90)) * 1e6
