"""Useful expert flops of the window over the chips' bf16 peak, in %.

Useful: the MMSE expert's count for every slot-UE (it runs densely as the
fail-safe baseline) plus the AI expert's count for every slot-UE the AI
expert served (its mode was AI and it did not overflow the gated
capacity).  The AI sub-batch's unused capacity is not counted.  Counts
from ``bench/work/``; served slot-UEs from the step's outputs."""

import numpy as np


def read(run):
    w = run.window
    if w is None or w.n_timed == 0:
        return None
    active = run.timed_outputs("active_mode")
    overflow = run.timed_outputs("gated_overflow")
    served_ai = int(np.sum((active == 0) & (overflow == 0)))
    flops = (active.size * run.work("mmse_interp").expert_flops_per_ue(
        run.config) + served_ai * run.work("ai_expert").flops_per_ue(
        run.config))
    peak = run.peaks["bf16_flops_per_s"] * run.chips
    return 100.0 * flops / (w.seconds * peak)
