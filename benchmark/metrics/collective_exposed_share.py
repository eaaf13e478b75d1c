"""Collective time during which the core runs nothing else, over the step, %."""
from benchmark.readers import collective_exposed_share as read  # noqa: F401
