"""Benchmark of the object store and its decode plane (see PERF.md)."""
