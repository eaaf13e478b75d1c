"""The engine's ``params`` build phase (``fam.init`` or the
checkpoint's load, the move to the device) less the compiles it caused,
which ``setup_compile_s`` holds: the eager ops' dispatch and the
device's work on the weights, s (``benchmark/reduce/setup.py``)."""
from benchmark.reduce.setup import read_setup_params_s as read  # noqa: F401
