"""Gaps between consecutive tokens of a request, pooled, median, ms."""
from benchmark import readers


def read(run):
    return readers.gap_ms(run, 50)
