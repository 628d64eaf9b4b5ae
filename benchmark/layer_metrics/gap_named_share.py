"""worker loop: share of the first device's idle time, in gaps of
2 us or more, that falls in gaps whose midpoint lies under a leaf
span of the program (``tm:``): how much of the idle time the
program's own spans can name."""
from ._program_spans import gap_named_share as read  # noqa: F401
