"""Device time of one fused-descent kernel call, from the trace (us)."""
from readings import kernel_us as read  # noqa: F401
