"""Chip benchmark of the ARCHES slot engine (see ``bench/run.py``)."""
