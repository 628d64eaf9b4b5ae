"""model step (models/base.py, models/llama.py): of the step program's
self time in instructions that do work, the share in instructions
with a block (``blk_*``, ``opt_update``, ``exchange_b<i>``): what
``gap_named_share`` is to the idle time, for the busy time."""
from ._blocks import named_share as read  # noqa: F401
