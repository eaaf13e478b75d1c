"""n * tokens_per_step / (t_n - t_0) / chips over whole fenced steps."""
from benchmark.readers import train_tokens_per_s_chip as read  # noqa: F401
