"""What ``device_stats.instrument``'s side compiles of fresh signatures
took before the window opened (lower, compile or cache read, the
compiled text's print and its scope map's parse), summed, s
(``benchmark/reduce/setup.py``)."""
from benchmark.reduce.setup import read_setup_harvest_s as read  # noqa: F401
