"""Device time with a collective in flight over the step, %."""
from benchmark.readers import collective_time_share as read  # noqa: F401
