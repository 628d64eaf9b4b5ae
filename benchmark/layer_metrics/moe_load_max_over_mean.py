"""moe (parallel/moe.py): the fullest expert's routed rows over the
mean, worst layer, of the run's last fenced step — the program's own
counter (``theanompi_tpu.obs.last_moe_counters``; 1.0 at balance),
or a recorded trace's ``"moe_counters"``."""


def read(facts):
    if "scan_k" not in facts:       # not a training run's facts
        return None
    counters = (facts.get("trace") or {}).get("moe_counters")
    if counters is None:
        try:
            from theanompi_tpu.obs import last_moe_counters
        except ImportError:         # a program from before PR 26
            return None
        counters = last_moe_counters()
    return counters["moe_load_max_over_mean"] if counters else None
