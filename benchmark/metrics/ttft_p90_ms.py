"""First token minus DUE time, 90th percentile over measured requests, ms."""
from benchmark import readers


def read(run):
    return readers.ttft_ms(run, 90)
