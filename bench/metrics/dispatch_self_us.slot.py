"""Median per slot of the program's ``arches.slot.dispatch`` host span
outside the runtime's execute call (``PJRT_LoadedExecutable_Execute``):
argument handling, the slot index's copy, rebuilding the outputs, from the
trace (``bench/harness/stages.py``)."""

from bench.harness import stages


def read(run):
    return stages.dispatch_us(run, "self")
