"""data (models/data/*, the ``_device_cache`` of models/base.py and
models/llama.py): set-up seconds of the train set — the program's
``setup.data`` phase (the data object: host generation) plus
``setup.stage_data`` (the device-resident copy with its cast), each
less the compile seconds inside it (``setup_compile_s`` has those)."""
from ._program_spans import setup_seconds


def read(facts):
    return setup_seconds(facts, ("setup.data", "setup.stage_data"))
