"""optimizer (parallel/plan.py ``ExchangePlan.apply``): device
milliseconds a step in instructions that carry the optimizer update —
the instruction, or one of its fused computation, lies under
``opt_update``.  It OVERLAPS the block metrics: a weight-gradient
product fused with its update counts under its block and here."""
from ._blocks import opt_ms as read  # noqa: F401
