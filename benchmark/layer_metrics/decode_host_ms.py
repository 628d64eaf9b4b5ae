"""decoder: what the host adds to a token — the median of the
engine's own decode-step time (``ServingRecorder`` ``dt_s``: uploads,
dispatch, the wait for the device, the read back, bookkeeping) less
the median device time of the decode program."""
from .. import trace_reduce as tr
from . import decode_device_ms


def read(facts):
    steps = facts.get("engine_steps")
    device_ms = decode_device_ms.read(facts)
    if not steps or device_ms is None:
        return None
    return 1e3 * tr.median([s["dt_s"] for s in steps]) - device_ms
