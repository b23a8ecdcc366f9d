"""Shared pieces of the benchmark: the cell loader, trace reduction, counters."""
