"""Per ``raytpu.engine.step`` with a decode wave and no prefill in the
traced window: its duration less its ``decode_fence``, median, ms."""
from benchmark.reduce.program import engine_host_ms_per_step as read  # noqa: F401
