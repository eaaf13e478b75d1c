"""Over the fences that waited for their program (by the ``seq`` the
fence span carries): the fence's return behind the program's end on
the device, median, ms, offset-corrected: the way back."""
from benchmark.reduce.launches import read_fence_return_lag_p50_ms as read  # noqa: F401
