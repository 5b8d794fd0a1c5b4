"""Median per slot of the runtime's output-buffer allocation
(``AllocateOutputBuffersWithInputReuse``, one device buffer per output
leaf) inside the program's ``arches.slot.dispatch`` host span, from the
trace (``bench/harness/stages.py``)."""

from bench.harness import stages


def read(run):
    return stages.dispatch_us(run, "alloc")
