"""Process start to the first measured step or request, s."""
from benchmark.readers import setup_s as read  # noqa: F401
