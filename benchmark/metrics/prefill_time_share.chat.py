"""Device time of the prefill programs over the traced window, %."""
from benchmark import readers


def read(run):
    return readers.program_time_share(run, readers.PREFILL_PROGRAM)
