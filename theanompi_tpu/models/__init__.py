"""Model zoo (reference: ``theanompi/models/`` — AlexNet, GoogLeNet,
VGG16, ResNet-50, Wide-ResNet, Lasagne LSTM/IMDB).

Every model satisfies the duck-typed contract the workers drive
(reference README): ``build_model()``, ``compile_iter_fns()``,
``train_iter(count, recorder)``, ``val_iter(count, recorder)``,
``adjust_hyperp(epoch)``, and attributes ``params``, ``data``,
``epoch``, ``n_epochs``.
"""

from __future__ import annotations

import importlib

# Flagship preference order of __graft_entry__:
# (modelfile, modelclass, config, per-chip batch).
FLAGSHIP_CANDIDATES = [
    (
        "theanompi_tpu.models.resnet50",
        "ResNet50",
        {"batch_size": 128, "compute_dtype": "bfloat16"},
        128,
    ),
    (
        "theanompi_tpu.models.wresnet",
        "WResNet",
        {"batch_size": 256, "depth": 28, "widen": 10,
         "compute_dtype": "bfloat16"},
        256,
    ),
]


def load_flagship():
    """→ (modelfile, modelclass, model_cls, bench_cfg, bench_batch) for
    the first importable flagship candidate."""
    for modelfile, modelclass, cfg, batch in FLAGSHIP_CANDIDATES:
        try:
            mod = importlib.import_module(modelfile)
        except ImportError:
            continue
        cls = getattr(mod, modelclass, None)
        if cls is not None:
            return modelfile, modelclass, cls, dict(cfg), batch
    raise RuntimeError("no flagship model importable")
