"""Gaps between consecutive tokens of a request, pooled, 95th percentile, ms."""
from benchmark import readers


def read(run):
    return readers.gap_ms(run, 95)
