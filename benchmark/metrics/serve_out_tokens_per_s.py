"""Tokens by their own emission time inside the window, per second."""
from benchmark.readers import serve_out_tokens_per_s as read  # noqa: F401
