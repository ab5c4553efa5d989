"""Chip benchmark of the DVBP placement engine (see ``BENCHMARK.json``)."""
