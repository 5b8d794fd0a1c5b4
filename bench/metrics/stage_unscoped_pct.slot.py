"""Share of the traced window's summed device-op time in ops under no
``arches.<stage>`` scope (copies, the slot index's transfer): what the six
``stage_us`` metrics leave out (``bench/harness/stages.py``)."""

from bench.harness import stages


def read(run):
    return stages.unscoped_pct(run)
