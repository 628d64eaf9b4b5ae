"""model step (ops/ssd.py ``ssd_scan``): the most negative cumulative
``dt A`` inside one chunk of the state-space scan in the run's last
fenced step, over the mamba layers — the program's own counter
(``ssm_log_decay_min``, ``theanompi_tpu.obs.last_ssm_counters``; a
recorded trace's ``"ssm_counters"``).  The chunked form only ever
exponentiates differences of such sums that are <= 0, so a large
negative number costs nothing but a decay that reads 0; it says how
far a factored form (``exp(cum_l) exp(-cum_s)``) would be from
float32's range (88).  ``None`` for a program without the counter."""


def read(facts):
    if "scan_k" not in facts:       # not a training run's facts
        return None
    counters = (facts.get("trace") or {}).get("ssm_counters")
    if counters is None:
        try:
            from theanompi_tpu.obs import last_ssm_counters
        except ImportError:         # a program from before PR 47
            return None
        counters = last_ssm_counters()
    if not counters or not counters.get("ssm_log_decay_min"):
        return None
    return min(counters["ssm_log_decay_min"])
