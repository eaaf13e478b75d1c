"""Bytes a decode step needs / 819 GB/s over its device time, %."""
from benchmark.readers import decode_hbm_roofline as read  # noqa: F401
