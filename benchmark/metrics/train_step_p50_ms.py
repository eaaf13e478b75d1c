"""Median fence-to-fence step time, ms."""
from benchmark import readers


def read(run):
    return readers.train_step_ms(run, 50)
