"""device (XLA TPU, libtpu): share of the traced window in which no
operation ran on the chip, in a training cell."""
from ._common import idle_share as read  # noqa: F401
