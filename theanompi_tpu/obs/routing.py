"""Routing counters of a mixture-of-experts training step.

A MoE ``Llama`` step gives out, beside its loss, ``[L, E+1]`` numbers
a layer: each expert's share ``f`` of the step's (token, pick) rows
(1/E at balance; the ``f`` of the load-balance loss) and the picks no
expert computed (0 on the dropless path by construction, counted on
the capacity path).  ``Recorder.moe_routing`` holds the device value
and reads it with the losses at its next fence — no fence and no host
sync of its own — then keeps the LAST step's counters here:

- ``moe_rows_per_expert`` ``[L][E]`` — routed rows of that step;
- ``moe_load_max_over_mean`` — the fullest expert's rows over the
  mean, worst layer (1.0 at balance; the grouped products' tail and,
  with capacity buffers, the drops follow it);
- ``moe_dropped_picks`` — summed over the layers;
- ``moe_experts_held`` and ``moe_rows_held`` ``[L]`` — where the
  layers' leaves hold only experts ``[0, held)`` of the ``E`` routed
  over (one expert-parallel rank's share): how many, and the rows
  those experts computed in that step, a layer (``picks * held / E``
  at balance).  ``f`` and with it ``moe_load_max_over_mean`` stay
  over all ``E``: the router is whole;
- ``moe_rows_bound`` and ``moe_held_overflow_layers`` — beside them:
  the static length ``R`` a held layer call lays its rows out at
  (``parallel.moe.held_rows_bound``, the function the layer itself
  uses, of the step's picks a layer) and how many layer calls of that
  step held more rows than ``R``, so took more than one pass of
  ``moe._held_windows``' loop over windows of ``R`` rows (0 near
  balance).  Exact where one shard holds the step's tokens; over
  ``dp`` data shards each lays out its own ``picks / dp`` rows and
  only their sums come here, so a call that ONE shard alone took
  further may not show;
- ``moe_bias_abs_max`` — a sigmoid router's selection bias after
  that step, its largest size over layers and experts (0 at the
  start, a rate a step at most).

The run summary carries them (``"moe_counters"``) and they stay
readable afterwards with :func:`last_moe_counters`.  Names are a
contract (docs/OBSERVABILITY.md).
"""

from __future__ import annotations

import numpy as np

_LAST: dict | None = None


def moe_counters(routing, picks: int, *, held: int | None = None,
                 bias_abs_max=None) -> dict:
    """``routing [L, E+1]`` (see the module docstring) of one step of
    ``picks`` (token, pick) rows a layer -> the counters' dict; also
    kept as the process's newest.  ``held``, ``bias_abs_max [L]``:
    what the last counters are made from, where the model has them."""
    global _LAST
    a = np.asarray(routing, np.float64)
    share, dropped = a[:, :-1], a[:, -1]
    rows = np.rint(share * picks).astype(int)
    _LAST = {
        "moe_picks_per_step": int(picks),
        "moe_rows_per_expert": rows.tolist(),
        "moe_load_max_over_mean": float(
            np.max(share.max(axis=1) / share.mean(axis=1))
        ),
        "moe_dropped_picks": int(round(float(dropped.sum()))),
    }
    if held is not None:
        # (imported here: this module is read before any model is)
        from theanompi_tpu.parallel.moe import held_rows_bound

        rows_held = rows[:, :held].sum(axis=1)
        bound = held_rows_bound(int(picks), int(held), share.shape[1])
        _LAST["moe_experts_held"] = int(held)
        _LAST["moe_rows_held"] = rows_held.tolist()
        _LAST["moe_rows_bound"] = bound
        _LAST["moe_held_overflow_layers"] = int((rows_held > bound).sum())
    if bias_abs_max is not None:
        _LAST["moe_bias_abs_max"] = float(np.max(bias_abs_max))
    return _LAST


def last_moe_counters() -> dict | None:
    """The routing counters of the newest fenced MoE step of this
    process, or None before any (a dense model never has one)."""
    return _LAST
