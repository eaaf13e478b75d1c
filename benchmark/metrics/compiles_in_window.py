"""XLA compiles (jax.monitoring) between window start and end."""
from benchmark.readers import compiles_in_window as read  # noqa: F401
