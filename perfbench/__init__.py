"""Benchmark of the OLA workload server on the chip (see PERF.md)."""
