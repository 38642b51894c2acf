"""Chip benchmark of the rDLB parallel loops (see ``BENCHMARK.json``)."""
