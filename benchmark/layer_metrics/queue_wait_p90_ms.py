"""serving engine: 90th percentile of the engine's ``engine_queue``
span (``obs/tracer.py``, on in the traced run), submit to admission
into a slot, over the window's completed requests."""
from ..drivers.open_loop import percentile


def read(facts):
    waits = facts.get("queued_s")
    if not waits:
        return None
    return 1e3 * percentile(waits, 90)
