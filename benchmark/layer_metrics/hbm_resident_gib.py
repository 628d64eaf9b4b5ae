"""device: GiB the fullest chip held when the run built its summary —
the state a step starts from (weights, optimizer state, staged data;
no program's temporaries) — by the program's own memory account
(``resident_bytes``, theanompi_tpu/obs/memory.py)."""
from ._memory import runtime_gib


def read(facts):
    return runtime_gib(facts, "resident_bytes")
