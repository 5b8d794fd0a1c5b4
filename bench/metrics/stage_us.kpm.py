"""Device time per slot of the ops under the program's ``arches.kpm`` scope
(the EVM at every order, the TB model, OLLA and the KPM report): device
seconds of those ops in the traced window over the window's slots
(``bench/harness/stages.py``)."""

from bench.harness import stages


def read(run):
    return stages.stage_us(run, "kpm")
