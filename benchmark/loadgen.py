"""Open-loop load: a schedule drawn from a seed, and a thread that
submits each request when it is due — whether or not earlier ones have
finished — and says how late it ran.

One generator reads every ``kind: open_loop`` traffic file; a new mix
is a new file of parameters, not new code:

    rate_rps            requests per second offered; the gaps between
                        arrivals are exponential (Poisson arrivals)
    prompt_tokens       {"median", "sigma", "min", "max"}: log-normal
    output_tokens       the same
    max_total_tokens    prompt + output is clipped to it (output gives)

Arrival times, lengths and token ids all come from ``--seed``: every
run of a cell meets another draw of the same mix, so no gate is tuned
to one schedule.
"""

from __future__ import annotations

import dataclasses
import math
import threading
import time

import numpy as np


@dataclasses.dataclass
class Planned:
    due_s: float            # offset from the window's start
    prompt: list
    max_tokens: int


def _lognormal(rng, spec: dict, n: int) -> np.ndarray:
    draw = rng.lognormal(math.log(spec["median"]), spec["sigma"], n)
    return np.clip(np.rint(draw), spec["min"], spec["max"]).astype(int)


def plan(traffic: dict, *, seed: int, seconds: float, vocab: int) -> list[Planned]:
    """Every request due inside ``[0, seconds)``, in order.  The same
    ``(traffic, seed, seconds, vocab)`` gives the same plan."""
    rng = np.random.default_rng([int(seed), 0x10AD])
    rate = float(traffic["rate_rps"])
    due = np.cumsum(rng.exponential(1.0 / rate, int(rate * seconds * 2 + 64)))
    due = due[due < seconds]
    n = len(due)
    p_len = _lognormal(rng, traffic["prompt_tokens"], n)
    o_len = _lognormal(rng, traffic["output_tokens"], n)
    o_len = np.minimum(o_len, int(traffic["max_total_tokens"]) - p_len)
    if (o_len < 1).any():
        raise ValueError("max_total_tokens leaves a request no output")
    return [
        Planned(float(due[i]),
                [int(t) for t in rng.integers(1, vocab, int(p_len[i]))],
                int(o_len[i]))
        for i in range(n)
    ]


class OpenLoop:
    """Submits a plan on its schedule from one thread.  ``submit`` is
    called as ``submit(planned)`` and returns whatever the system
    gives back for the request (a future); ``sent`` pairs each planned
    request with it and with the seconds by which its submission ran
    late."""

    def __init__(self, planned: list[Planned], submit):
        self._planned = planned
        self._submit = submit
        self.sent: list[tuple[Planned, object, float]] = []
        self.t0: float | None = None
        self._thread = threading.Thread(
            target=self._run, name="bench-loadgen", daemon=True
        )

    def start(self) -> float:
        self.t0 = time.monotonic()
        self._thread.start()
        return self.t0

    def _run(self) -> None:
        for p in self._planned:
            wait = self.t0 + p.due_s - time.monotonic()
            if wait > 0:
                time.sleep(wait)
            late = time.monotonic() - (self.t0 + p.due_s)
            self.sent.append((p, self._submit(p), max(0.0, late)))

    def join(self, timeout: float) -> bool:
        self._thread.join(timeout)
        return not self._thread.is_alive()

    def lateness(self) -> dict:
        late = sorted(s[2] for s in self.sent)
        if not late:
            return {"n": 0}
        return {
            "n": len(late),
            "median_ms": 1e3 * late[len(late) // 2],
            "max_ms": 1e3 * late[-1],
        }
