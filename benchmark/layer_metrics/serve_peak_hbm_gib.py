"""device: peak bytes in use on the chip after the window, in a
serving cell (weights and block pool; guards the sizing)."""
from ._common import peak_gib as read  # noqa: F401
