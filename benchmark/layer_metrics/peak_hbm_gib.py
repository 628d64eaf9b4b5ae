"""device: peak bytes in use on the fullest chip after the window, in
a training cell (room for batch; guards the sizing)."""
from ._common import peak_gib as read  # noqa: F401
