"""worker loop: of the device-idle gap between two runs of the step
program on the first device (``dispatch_gap_ms``), the part during
which the host was still inside ``tm:worker.fence`` — the device has
finished, the fence has not returned yet (read-back of the losses,
the waiting thread's wake-up); median over the gaps."""
from ._program_spans import FENCE, boundary_ms


def read(facts):
    return boundary_ms(facts, FENCE)
