"""The benchmark's general machinery: cells, traffic, traces, reference."""
