"""Device time of the prefill and chunk executions the traced window
holds whole, joined to their launch records, over the prompt tokens
those records say they prefilled (``n_tail`` summed), ms a thousand
tokens (``benchmark/reduce/launches.py``)."""
from benchmark.reduce.launches import read_prefill_device_ms_per_ktoken as read  # noqa: F401
