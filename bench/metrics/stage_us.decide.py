"""Device time per slot of the ops under the program's ``arches.decide``
scope (the KPM window, the policy's decision, the switch register and the
breaker): device seconds of those ops in the traced window over the
window's slots (``bench/harness/stages.py``)."""

from bench.harness import stages


def read(run):
    return stages.stage_us(run, "decide")
