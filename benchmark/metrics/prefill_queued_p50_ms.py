"""A prefill's start on the device behind the end of its dispatch span,
median over the traced window's prefills, ms: what it waited behind the
launches the engine keeps in flight (its record's ``ahead``; with none
ahead it is a launch lag)."""
from benchmark.reduce.launches import read_prefill_queued_p50_ms as read  # noqa: F401
