"""model step: seconds of the set-up inside JAX's backend compile
path (compiling, or loading from the persistent cache), by the
program's own compile counter (``theanompi_tpu/obs/compile_meter.py``)
from the worker's entry to the first fence."""
from ._program_spans import setup_phases


def read(facts):
    phases = setup_phases(facts)
    if phases is None:
        return None
    return sum(p["compile_s"] for p in phases.values())
