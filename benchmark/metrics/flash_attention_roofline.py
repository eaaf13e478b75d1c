"""Least possible time for the attention needed over Mosaic time, %."""
from benchmark.readers import flash_attention_roofline as read  # noqa: F401
