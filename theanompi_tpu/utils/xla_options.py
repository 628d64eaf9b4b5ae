"""Per-executable XLA compiler options (the TPU flag surface).

TPU options travel per jit as ``compiler_options``, not as
``XLA_FLAGS``: the flags are process-wide, and the CPU client — which
every TPU process also creates — aborts on an unknown ``--xla_tpu_*``
flag.  One helper so every compile site (models, workers)
honors the same knobs:

- ``config["xla_options"]`` — dict of option name → value, or a
  ``"k=v,k2=v2"`` string
- ``TM_XLA_OPTIONS`` env — same string form

Config and env merge PER KEY, config winning on collisions: a sweep
setting one env knob keeps it even when the model config carries its
own options dict (pre-bucketing behavior silently dropped the whole
env dict whenever the config had any options at all).

Example: ``TM_XLA_OPTIONS=xla_tpu_scoped_vmem_limit_kib=65536``.
"""

from __future__ import annotations

import os
from typing import Any, Optional


def _parse(spec: str) -> dict[str, str]:
    out: dict[str, str] = {}
    for item in spec.split(","):
        item = item.strip()
        if not item:
            continue
        if "=" not in item:
            raise ValueError(
                f"TM_XLA_OPTIONS entry {item!r} is not k=v"
            )
        k, v = item.split("=", 1)
        out[k.strip().lstrip("-")] = v.strip()
    return out


def overlap_preset() -> dict[str, str]:
    """Compiler options that feed XLA's collective/compute overlap
    machinery — what makes the bucketed exchange schedule actually
    hide wire time (``parallel/exchange`` bucketed paths): async
    collectives give each bucket's reduce-scatter/all-gather a
    dispatch/done pair the scheduler can split, and the
    latency-hiding scheduler moves independent compute (other
    buckets' pack/update, the backward tail) between them.

    Applied PER-JIT (``xla_compiler_options(..., overlap=True)``);
    the caller gates on the mesh actually being TPU — the CPU client
    rejects unknown ``xla_tpu_*`` options.  Explicit config/env
    settings of the same keys win over the preset.
    """
    return {
        "xla_tpu_enable_latency_hiding_scheduler": "true",
        "xla_tpu_enable_async_collective_fusion": "true",
        "xla_tpu_enable_async_collective_fusion_fuse_all_gather": "true",
    }


def xla_compiler_options(
    config: dict | None = None,
    *,
    overlap: bool = False,
) -> Optional[dict[str, Any]]:
    """Resolve compiler options from config/env; None when nothing is
    set (so jit calls stay identical to the no-knob path and
    compile-cache keys don't churn).

    Precedence per key, lowest to highest: ``overlap_preset()`` (when
    ``overlap=True``), ``TM_XLA_OPTIONS`` env, ``config["xla_options"]``.
    """
    out: dict[str, Any] = dict(overlap_preset()) if overlap else {}
    env = os.environ.get("TM_XLA_OPTIONS", "")
    if env:
        out.update(_parse(env))
    cfg = (config or {}).get("xla_options")
    if isinstance(cfg, str):
        out.update(_parse(cfg))
    elif isinstance(cfg, dict):
        out.update({str(k).lstrip("-"): v for k, v in cfg.items()})
    return out or None
