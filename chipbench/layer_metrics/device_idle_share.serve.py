"""Share of the traced slice of a serving cell in which no operation ran on the
device.  device_trace."""
from readers import device_idle_share as read  # noqa: F401
