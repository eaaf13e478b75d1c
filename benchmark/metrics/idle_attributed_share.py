"""Device idle seconds of the window under a leaf ``raytpu.engine.*``
phase in which the host works (split by overlap; the fences and the
loop's own fragments stay out) over all idle seconds, %."""
from benchmark.reduce.program import idle_attributed_share as read  # noqa: F401
