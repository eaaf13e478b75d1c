"""Rows decoding per wave over max_slots, mean over the window, %."""
from benchmark.readers import slot_occupancy as read  # noqa: F401
