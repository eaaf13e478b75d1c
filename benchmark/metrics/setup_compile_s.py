"""Tracing, lowering and the backend's part (the cache's read or the
real compile) of every program compiled or loaded before the window
opened that no ``instrument`` side compile caused, summed, s
(``benchmark/reduce/setup.py``)."""
from benchmark.reduce.setup import read_setup_compile_s as read  # noqa: F401
