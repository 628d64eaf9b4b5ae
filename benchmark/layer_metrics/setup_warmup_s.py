"""worker loop (workers/bsp_worker.py): the program's
``setup.warmup`` phase — the first epoch from its first dispatch to
the first fence: tracing and lowering the step, then the step
program's first run — less the compile seconds inside it
(``setup_compile_s`` has those)."""
from ._program_spans import setup_seconds


def read(facts):
    return setup_seconds(facts, ("setup.warmup",))
