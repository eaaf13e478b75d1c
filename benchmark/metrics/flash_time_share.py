"""Mosaic (flash attention) device time over the step, %."""
from benchmark.readers import flash_time_share as read  # noqa: F401
