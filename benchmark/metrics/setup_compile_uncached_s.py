"""Of ``setup_compile_s``, the backend's seconds of the programs the
persistent cache did not serve: from a warm cache the sub-second
programs alone; a side that compiles anew what the other side loaded
shows here, by name in the ``[setup_records]`` line
(``benchmark/reduce/setup.py``)."""
from benchmark.reduce.setup import read_setup_compile_uncached_s as read  # noqa: F401,E501
