"""Programs JAX compiled or loaded before the window opened: the
program's ``compile`` set-up records closed by then, as a count
(``benchmark/reduce/setup.py``)."""
from benchmark.reduce.setup import read_setup_compiles as read  # noqa: F401
