"""Slot-seconds decoding rows stood behind a prefill: over the prefill
and chunk executions of the traced window, device time times the
launch record's ``rows`` (the rows decoding when it was dispatched),
over the window times ``max_slots``, %."""
from benchmark.reduce.launches import read_decode_rows_stalled_share as read  # noqa: F401
