"""Admit minus enqueue from the engine's records, median, ms."""
from benchmark import readers


def read(run):
    return readers.queue_wait_ms(run, 50)
