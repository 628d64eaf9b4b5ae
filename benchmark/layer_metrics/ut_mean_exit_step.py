"""loss (models/llama.py ``_exit_loss``): the mean exit step ``sum_t t
* q_t`` of the run's last fenced step, 1..R — the program's own
counter (``theanompi_tpu.obs.last_ut_counters``; 1.875 of 4 at a zero
gate), or a recorded trace's ``"ut_counters"``.  It shows that the
gate lives and moves; nobody should optimise it alone."""


def read(facts):
    if "scan_k" not in facts:       # not a training run's facts
        return None
    counters = (facts.get("trace") or {}).get("ut_counters")
    if counters is None:
        try:
            from theanompi_tpu.obs import last_ut_counters
        except ImportError:         # a program from before PR 33
            return None
        counters = last_ut_counters()
    return counters["ut_mean_exit_step"] if counters else None
