"""Device time of the decode program per call, median, ms."""
from benchmark import readers


def read(run):
    return readers.program_ms(run, readers.DECODE_PROGRAM, 50)
