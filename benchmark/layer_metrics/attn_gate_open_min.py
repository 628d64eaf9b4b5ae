"""model step (models/llama.py ``Llama._attn_gate``): the least, over
the gated attention calls of the run's last fenced step, of the mean
sigmoid gate over that call's tokens and query heads — the program's
own counter (``attn_gate_open``,
``theanompi_tpu.obs.last_gate_counters``; a recorded trace's
``"attn_gate_counters"``).  0.5 at a seed's weights; a layer near 0 has
switched its attention block off.  It shows that the gate lives:
nobody should optimise it alone.  ``None`` for a program without the
counter."""


def read(facts):
    if "scan_k" not in facts:       # not a training run's facts
        return None
    counters = (facts.get("trace") or {}).get("attn_gate_counters")
    if counters is None:
        try:
            from theanompi_tpu.obs import last_gate_counters
        except ImportError:         # a program from before PR 50
            return None
        counters = last_gate_counters()
    if not counters or not counters.get("attn_gate_open"):
        return None
    return min(counters["attn_gate_open"])
