"""Prompt tokens served from resident KV blocks over prompt tokens, %."""
from benchmark.readers import prefix_hit_share as read  # noqa: F401
