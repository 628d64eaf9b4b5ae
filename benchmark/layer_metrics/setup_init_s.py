"""model step (models/base.py, models/llama.py): set-up seconds of
building the model — the program's ``setup.build_model`` phase
(``Model(cfg)`` and ``build_model``: the network, and ResNet's weights
leaf by leaf) plus ``setup.compile_iter_fns`` (step functions built,
weights and optimizer state placed; Llama makes its weights there,
under ``jit``), less the data phases nested in them and less the
compile seconds inside them (``setup_compile_s`` has those)."""
from ._program_spans import setup_seconds


def read(facts):
    return setup_seconds(
        facts, ("setup.build_model", "setup.compile_iter_fns"))
