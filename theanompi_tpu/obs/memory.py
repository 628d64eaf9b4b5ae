"""Where a training run's device memory stands: one account a run,
the keep rule's side from shapes and the runtime's side from a dozen
``memory_stats()`` readings, none of them in the steady loop.

``workers/bsp_worker.run`` opens the account
(:func:`begin_memory_account`) once its mesh names the run's devices
and takes a sample (:meth:`MemoryAccount.sample`: every device, the
fullest named)

- at the end of each set-up phase ``obs/setup.py`` has (the record's
  ``on_phase_end``; a nested phase as it closes, under its own name),
- at the first fence (``"first_fence"``: the step program has run
  once), and
- once when it builds its summary (``"summary"``: what a step starts
  from),

and nowhere else: not in ``Recorder.fence``, not in ``train_chunk``.
:func:`read_memory_stats` is the program's ONE call of
``memory_stats()``; ``models/llama.py`` reads its ``bytes_limit``
through :func:`device_bytes_limit`.

The rule's side (``rule``) is what the model's keep rule decided from
and left (``Llama.keep_account``: the limit, the reserve, the
estimate's terms, the bytes kept and unkept, the calls a kind); None
for a model without such a rule and where it does not run.

The runtime's side, as this libtpu fills the fields
(docs/OBSERVABILITY.md, "Memory account", has the raw readings of the
chip runs it rests on): ``bytes_in_use`` is every live buffer of the
process on the device (weights, optimizer state, staged data) AND the
code of the programs it has loaded; ``bytes_reserved`` is what the
runtime has set aside for the temporaries of those programs — 0 until
the step program's first run, the step's temporaries from then on, also
between two runs and after the last; ``peak_bytes_in_use`` and
``peak_bytes_reserved`` are the most each has read so far, at moments
that need not coincide; ``largest_free_block_bytes`` is the largest
run of free bytes (``bytes_limit`` less the two, but for the hole a
freed set-up buffer left).  From the samples:

- ``resident_bytes`` — the fullest device's ``bytes_in_use`` at the
  last sample: what a step starts from;
- ``step_peak_bytes`` — ``resident_bytes`` + ``bytes_reserved`` at that
  sample: the most the steady step holds, without what only staging or
  the warm-up held (a second copy of the staged train set).

Both are current readings, so neither passes the sum of the same
device's two peak fields (``benchmark/run.py`` ``memory_peak_bytes``,
the benchmark's ``peak_hbm_gib``), which every sample carries: where
the most was held, and when each peak field rose, reads off
``samples``.

The account goes into ``run``'s summary (``"memory"``) and stays
readable afterwards with :func:`last_memory_account`.  It counts its
samples and the host seconds they cost (``n_samples``, ``sample_s``):
always on.
"""

from __future__ import annotations

import time

FIELDS = ("bytes_in_use", "peak_bytes_in_use", "bytes_reserved",
          "peak_bytes_reserved", "bytes_limit", "largest_free_block_bytes")
#: the names of the two samples outside the set-up phases
FIRST_FENCE, SUMMARY = "first_fence", "summary"


def read_memory_stats(devices) -> list[dict] | None:
    """``memory_stats()`` of every device, in order: ``{"device": id,
    **FIELDS}``, a key the runtime lacks ``None``.  ``None`` whole
    where a device gives nothing (the CPU) or cannot be asked (a
    described device, which has no runtime)."""
    import jax

    out = []
    for i, d in enumerate(devices):
        try:
            stats = d.memory_stats()
        except jax.errors.JaxRuntimeError:
            return None
        if not stats:
            return None
        out.append({"device": getattr(d, "id", i),
                    **{k: stats.get(k) for k in FIELDS}})
    return out or None


def device_bytes_limit(devices) -> int | None:
    """The least ``bytes_limit`` the devices' runtimes report; None
    where one reports none."""
    stats = read_memory_stats(devices)
    if stats is None:
        return None
    limits = [s["bytes_limit"] for s in stats]
    return min(limits) if all(limits) else None


class MemoryAccount:
    def __init__(self, devices, clock=time.monotonic):
        self.devices = list(devices)
        self.clock = clock
        self.t0 = clock()
        #: the keep rule's side (``Llama.keep_account``), set by the
        #: worker when it builds its summary
        self.rule: dict | None = None
        #: the samples the runtime answered
        self.samples: list[dict] = []
        self.n_samples = 0
        self.sample_s = 0.0

    def sample(self, at: str) -> None:
        """Read every device and keep the reading under ``at``."""
        t = self.clock()
        stats = read_memory_stats(self.devices)
        self.n_samples += 1
        if stats is not None:
            fullest = max(stats, key=lambda s: s["bytes_in_use"] or 0)
            self.samples.append({"at": at, "t": t - self.t0,
                                 "fullest": fullest["device"],
                                 "devices": stats})
        self.sample_s += self.clock() - t

    def _fullest(self) -> dict | None:
        """The fullest device's reading at the last sample."""
        if not self.samples:
            return None
        last = self.samples[-1]
        return next(d for d in last["devices"]
                    if d["device"] == last["fullest"])

    def resident_bytes(self) -> int | None:
        last = self._fullest()
        return None if last is None else last["bytes_in_use"]

    def step_peak_bytes(self) -> int | None:
        """``resident_bytes`` plus the reservation for program
        temporaries that stands beside it at the last sample."""
        last = self._fullest()
        if last is None:
            return None
        return last["bytes_in_use"] + (last["bytes_reserved"] or 0)

    def as_dict(self) -> dict:
        return {
            "rule": self.rule,
            "n_devices": len(self.devices),
            "n_samples": self.n_samples,
            "sample_s": self.sample_s,
            "samples": self.samples,
            "resident_bytes": self.resident_bytes(),
            "step_peak_bytes": self.step_peak_bytes(),
        }

    def format(self) -> str:
        """One line for the worker's log."""
        def gib(b):
            return "none" if b is None else f"{b / 2 ** 30:.3f}"

        line = (f"memory: resident {gib(self.resident_bytes())} GiB, step "
                f"peak {gib(self.step_peak_bytes())}; {self.n_samples} "
                f"samples in {self.sample_s * 1e3:.2f} ms")
        if self.rule:
            r = self.rule
            line += (f"; keep rule: estimate {gib(sum(r['terms'].values()))}"
                     f" + kept {gib(r['kept_bytes'])}, unkept "
                     f"{gib(r['unkept_bytes'])}, free {gib(r['free_bytes'])}")
        return line


#: the newest account of this process
_LAST: MemoryAccount | None = None


def begin_memory_account(devices) -> MemoryAccount:
    """Open the process's memory account over a run's devices."""
    global _LAST
    _LAST = MemoryAccount(devices)
    return _LAST


def last_memory_account() -> dict | None:
    """The memory account of the newest run of this process (the form
    of :meth:`MemoryAccount.as_dict`), or None before any."""
    return None if _LAST is None else _LAST.as_dict()
