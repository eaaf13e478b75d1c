"""Required FLOPs per token * tokens/s/chip over the bf16 peak, %."""
from benchmark.readers import train_mfu as read  # noqa: F401
