"""Median device time of one run of the engine's step program in the traced
slice (the XLA-modules line).  device_trace."""
from readers import step_device_ms as read  # noqa: F401
