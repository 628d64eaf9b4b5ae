"""worker loop (workers/bsp_worker.py): set-up seconds before the
worker's entry — the program's three process stamps
(``theanompi_tpu.obs.last_process_phases``): the process's start to
``import theanompi_tpu``, that import, and its end to
``bsp_worker.run``."""


def read(facts):
    if "scan_k" not in facts:       # not a training run's facts
        return None
    try:
        from theanompi_tpu.obs import last_process_phases
    except ImportError:             # a program from before PR 35
        return None
    phases = last_process_phases()
    if not phases or None in phases.values():
        return None
    return sum(phases.values())
