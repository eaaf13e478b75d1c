"""Sent minus due, 95th percentile, ms: how late the load generator ran."""
from benchmark import readers


def read(run):
    return readers.generator_lag_ms(run, 95)
