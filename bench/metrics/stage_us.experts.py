"""Device time per slot of the ops under the program's ``arches.experts``
scope (the expert bank: the AI expert, MMSE interpolation, the gated
compaction and the switch): device seconds of those ops in the traced
window over the window's slots (``bench/harness/stages.py``)."""

from bench.harness import stages


def read(run):
    return stages.stage_us(run, "experts")
