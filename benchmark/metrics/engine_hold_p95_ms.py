"""Time the engine's loop ran between the end of one
``raytpu.engine.yield`` and the start of the next, 95th percentile
over the traced window, ms: what a caller on the engine's loop waits
before its request is heard."""
from benchmark.reduce.launches import read_engine_hold_p95_ms as read  # noqa: F401
