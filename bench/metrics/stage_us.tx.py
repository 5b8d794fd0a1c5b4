"""Device time per slot of the ops under the program's ``arches.tx`` scope
(link adaptation, payload bits, QAM mapping at every order and the
resource grid): device seconds of those ops in the traced window over the
window's slots (``bench/harness/stages.py``)."""

from bench.harness import stages


def read(run):
    return stages.stage_us(run, "tx")
