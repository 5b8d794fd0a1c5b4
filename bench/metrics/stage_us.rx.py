"""Device time per slot of the ops under the program's ``arches.rx`` scope
(the LS estimate, the equalizer and the data-RE extraction): device
seconds of those ops in the traced window over the window's slots
(``bench/harness/stages.py``)."""

from bench.harness import stages


def read(run):
    return stages.stage_us(run, "rx")
