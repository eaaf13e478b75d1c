"""1 - union of device op intervals / traced window, %."""
from benchmark.readers import device_idle_share as read  # noqa: F401
