"""Over the launches whose execution began on an idle device (no
program ran in the 50 us before it): its start behind the end of its
dispatch span, median, ms, with the device clock's offset taken from
the window's own bracket (``benchmark/reduce/launches.py``)."""
from benchmark.reduce.launches import read_launch_lag_p50_ms as read  # noqa: F401
