"""worker loop: median length of ``tm:worker.dispatch`` — the HOST's
time inside the call of the jitted step until it returns, not the
step's device time (``step_device_ms``)."""
from ._program_spans import DISPATCH, span_ms


def read(facts):
    return span_ms(facts, DISPATCH)
