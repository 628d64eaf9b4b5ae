"""The builder's check of the ``mellum`` cell against its plain
reference at the PUBLISHED widths, outside any timed window (guide
``model-configs`` section 3, point 3; after ``tools/glm_check.py``):

    python3 -m benchmark.tools.mellum_check [--cell mellum2_train_t8192]
        [--seed n] [--variant NAME ... | --variant all] [--control]

One batch of the cell (2 x 8192 tokens) goes through the model's own
train step — built from the cell's configuration with plain SGD at a
power-of-two rate in place of Adam, so that ``(before - after) / rate``
IS the step's gradient — and through ``reference/mellum_moe.py`` in
float32 at ``highest`` precision, on the same weights, with the same
held range (16 of 64 experts), vocabulary slice, layer kinds, window
and rotary tables.  The reference runs first, a sequence at a time
and inside it a layer call at a time (``block=jax.checkpoint``); then
the program, and after it every ``--variant``, each a build of the
PROGRAM that is wrong on purpose while the reference stays right.
Held, each against a written limit:

- the step's loss (cross-entropy + 0.001 x the balance loss);
- every leaf's gradient, by the norm of the difference over the
  reference's norm; the routers' comes from the balance loss alone (a
  share by itself cuts the gates' gradient, ``parallel/moe.py``), in
  the reference as in the program;
- the routing counters: every expert's picks in every layer over ALL
  64, by the largest difference over the mean load.

The variants (``VARIANTS``): ``no_yarn`` (the full layer rotates by
the default table), ``no_factor`` (YaRN's frequencies without its
factor on cos and sin), ``full_table_on_window`` (the window layers
rotate by the full layer's table), ``pattern_shifted`` (the full layer
one place early), ``not_renormalised`` (the 8 picked gates as the
softmax gave them), ``no_balance_loss`` (the routers get no gradient
at all), and the window off by one either way (``window_1023``,
``window_1025``).  ``--variant all`` runs those that bf16 arithmetic
on the chip can tell from the right program; the window off by one
moves one key of 1024 a query — some 3 % of a window layer's output at
initialisation, inside bf16's own scatter of the gradients — and is
held on the CPU in float32 (``tests/test_mellum_moe.py``); asked for
by name here it is reported like the others.  The last line is a JSON
object with every number, ``ok`` of the right program and ``failed``
of each variant; the exit code is 0 when the right program passed and
every variant asked for failed.

``--control`` puts the REFERENCE ITSELF, computed in the nearest
precision below the cell's bf16 (``glm_check.lower_precision``: 3
mantissa bits), in the program's place: it has to fail a limit as a
wrong program does, or the limits would pass any arithmetic.

The limits.  Each lies between two readings on the chip (my chip run
A, PR 41, seed 2147489431): the largest the right program read, and
what the REFERENCE ITSELF read at 3 mantissa bits (``--control``); the
least a wrong program read is given too, and the limit lies under it.
bf16 flips some of the top-8's near-ties, and a flipped pick moves a
row into or out of the held range: hence gradients to a tenth and
counts to a twentieth, not to float32's 1e-4.

- ``LOSS_RTOL`` 2e-4, as ``drivers/train.py``'s (right 7.9e-6; the
  control 2.2e-5; the wrong ones 2.8e-6 to 1.5e-4: at initialisation
  the loss is ln(V) whatever the architecture, which is why the loss
  alone proves little).
- ``GRAD_RTOL`` 0.15 of the worst leaf's norm among the leaves no
  routed pick feeds directly, the routers among them (right 0.079,
  ``layers.1.wq``; its routers 0.013; the control 0.371; no factor
  0.415, not renormalised 0.522, no YaRN 0.822, no balance loss 1.0 —
  the routers get nothing —, the pattern shifted 3.27, the full table
  on the window layers 3.85).
- ``GRAD_RTOL_ROUTED`` 0.15 for the held experts (right 0.073; the
  control 0.287; no factor 0.298, no YaRN 0.505, not renormalised
  0.552, the pattern shifted 1.42, the full table on the window
  layers 1.48; no balance loss leaves them alone).
- ``COUNT_RTOL`` 0.12 of the mean load (right 0.050; the control
  0.373; no factor 0.424, no YaRN 0.517, not renormalised 0.747, the
  full table on the window layers 1.26, the pattern shifted 1.99).
"""

from __future__ import annotations

import argparse
import importlib
import json
import sys

from .glm_check import CONTROL, _rel0, _routed, lower_precision
from .olmoe_check import _flat

LOSS_RTOL = 2e-4
GRAD_RTOL = 0.15            # leaves no routed pick feeds directly
GRAD_RTOL_ROUTED = 0.15     # the held experts
COUNT_RTOL = 0.12
SGD_RATE = 2.0 ** 10


def _with(kind, **over):
    """``rope_parameters`` with one kind's entry changed."""
    def change(cfg):
        tables = dict(cfg["rope_parameters"])
        tables[kind] = dict(tables[kind], **over)
        return {"rope_parameters": tables}
    return change


#: variant -> what it changes of the program's configuration
VARIANTS = {
    "no_yarn": _with("full_attention", rope_type="default"),
    "no_factor": _with("full_attention", attention_factor=1.0),
    "full_table_on_window": lambda cfg: {"rope_parameters": dict(
        cfg["rope_parameters"],
        sliding_attention=cfg["rope_parameters"]["full_attention"])},
    "pattern_shifted": lambda cfg: {
        "layer_types": list(cfg["layer_types"][1:])},
    "not_renormalised": lambda cfg: {"moe_renormalize": False},
    "no_balance_loss": lambda cfg: {"moe_aux_coef": 0.0},
    "window_1023": lambda cfg: {"sliding_window": cfg["sliding_window"] - 1},
    "window_1025": lambda cfg: {"sliding_window": cfg["sliding_window"] + 1},
}
#: what ``--variant all`` runs (module docstring)
SEPARABLE = ("no_yarn", "no_factor", "full_table_on_window",
             "pattern_shifted", "not_renormalised", "no_balance_loss")


def _program_step(config, cfg, p0, batch):
    """(loss, gradients, pick counts [L, E]) of one SGD step of the
    program built from ``cfg``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from theanompi_tpu.parallel import make_mesh

    cls = getattr(importlib.import_module(config["model"]["modelfile"]),
                  config["model"]["modelclass"])
    model = cls(cfg)
    model.build_model(n_replicas=1)
    model.compile_iter_fns(mesh=make_mesh(data=1, devices=jax.devices()[:1]))
    model.params = jax.device_put(p0, model._shardings(model._specs))
    p1, _, _, loss, _, routing = model._train_step(
        model.params, model.opt_state, model.ef_state,
        *model.put_batch(batch), jnp.float32(SGD_RATE))
    loss = float(loss)
    grads = jax.tree.map(lambda a, b: (a - np.asarray(b)) / SGD_RATE,
                         p0, jax.device_get(p1))
    picks = batch[0].size * int(cfg["moe_top_k"])
    counts = np.rint(np.asarray(routing, np.float64)[:, :-1] * picks)
    model.params = p1 = None
    jax.clear_caches()
    return loss, grads, counts


def check(cell_name: str, seed: int, variants: list[str],
          rehearsal: bool = False, control: bool = False) -> dict:
    import jax
    import numpy as np

    from ..drivers.train import program_config
    from ..run import load_cell

    from theanompi_tpu.parallel import make_mesh

    config = load_cell(cell_name)["config"]
    if rehearsal:
        config = dict(config, **config["rehearsal"])
    cfg = dict(program_config(config, seed=seed, n_replicas=1),
               optimizer="sgd", device_data_cache=False)
    ref_spec = config["reference"]
    ref = importlib.import_module(
        f"{__package__.rsplit('.', 1)[0]}.reference.{ref_spec['module']}")
    kw = ref_spec["kwargs"]
    held = int(cfg["moe_experts_held"])

    # the weights and the batch every build starts from
    model = getattr(importlib.import_module(config["model"]["modelfile"]),
                    config["model"]["modelclass"])(cfg)
    model.build_model(n_replicas=1)
    model.compile_iter_fns(mesh=make_mesh(data=1, devices=jax.devices()[:1]))
    p0 = jax.tree.map(np.asarray, jax.device_get(model.params))
    model.data.shuffle(0)
    batch = tuple(np.asarray(a) for a in model.data.train_batch(0))
    model.params = model.opt_state = None
    del model
    jax.clear_caches()

    x, y = batch

    def reference_step():
        """(loss, gradients, pick counts) of the reference as the
        module stands, a sequence at a time.  The balance loss pools
        its moments over the batch: the sequences' ``f`` and ``P``
        are summed here and the loss formed from the sums, as
        ``loss_and_counts`` forms it for a batch."""
        import jax.numpy as jnp

        def parts(p, ids, tgt):
            with jax.default_matmul_precision("highest"):
                ce, counts, gsums = ref._sequence(
                    p, ids, tgt, dict(kw, block=jax.checkpoint))
            return ce, counts, gsums

        n = x.size
        e = int(cfg["n_experts"])
        # pass 1: the batch's pick fractions (no gradient flows there)
        first = jax.jit(parts)
        counts = sum(np.asarray(first(p0, ids, tgt)[1], np.float64)
                     for ids, tgt in zip(x, y))
        f = jnp.asarray(counts / (n * kw["top_k"]), jnp.float32)

        def seq_loss(p, ids, tgt):
            ce, _, gsums = parts(p, ids, tgt)
            lb = jnp.mean(e * jnp.sum(f * gsums / n, axis=-1))
            return ce / n + kw["aux_coef"] * lb

        one = jax.jit(jax.value_and_grad(seq_loss))
        loss, grads = 0.0, None
        for ids, tgt in zip(x, y):
            l, g = one(p0, ids, tgt)
            g = jax.tree.map(np.asarray, g)
            grads = g if grads is None else jax.tree.map(np.add, grads, g)
            loss += float(l)
        del one, first
        jax.clear_caches()
        return loss, grads, counts

    ref_loss, ref_grads, ref_counts = reference_step()
    ref_flat = _flat(ref_grads)

    def program_step(name):
        if name == CONTROL:
            with lower_precision(ref):
                return reference_step()
        over = VARIANTS[name](cfg) if name else {}
        return _program_step(config, dict(cfg, **over), p0, batch)

    def held_to_reference(name):
        loss, grads, counts = program_step(name)
        flat = _flat(grads)
        grad_rel = {k: _rel0(flat[k], ref_flat[k]) for k in flat}
        got = {
            "loss": loss,
            "loss_rel": abs(loss - ref_loss) / abs(ref_loss),
            "grad_rel_worst": max(
                v for k, v in grad_rel.items() if not _routed(k)),
            "grad_rel_worst_leaf": max(
                (k for k in grad_rel if not _routed(k)), key=grad_rel.get),
            "grad_rel_worst_routed": max(
                v for k, v in grad_rel.items() if _routed(k)),
            "grad_rel_router": max(
                v for k, v in grad_rel.items() if "router" in k),
            "count_rel_worst": float(
                np.max(np.abs(counts - ref_counts)) / ref_counts.mean()),
            "picks_an_expert_max_over_mean": float(
                counts.max() / counts.mean()),
            "rows_held": counts[:, :held].sum(axis=1).tolist(),
        }
        got["ok"] = bool(
            got["loss_rel"] <= LOSS_RTOL
            and got["grad_rel_worst"] <= GRAD_RTOL
            and got["grad_rel_worst_routed"] <= GRAD_RTOL_ROUTED
            and got["count_rel_worst"] <= COUNT_RTOL
        )
        print(json.dumps({"variant": name, **got}), flush=True)
        return dict(got, grad_rel=grad_rel)

    out = {
        "cell": cell_name, "seed": seed,
        "device": {"platform": jax.devices()[0].platform,
                   "kind": jax.devices()[0].device_kind},
        "tokens": int(x.size), "reference_loss": ref_loss,
        "reference_rows_held": ref_counts[:, :held].sum(axis=1).tolist(),
        "limits": {"loss_rel": LOSS_RTOL, "grad_rel": GRAD_RTOL,
                   "grad_rel_routed": GRAD_RTOL_ROUTED,
                   "count_rel": COUNT_RTOL},
        "right": held_to_reference(None),
        "variants": {name: held_to_reference(name)
                     for name in [*variants, *([CONTROL] if control else [])]},
    }
    out["ok"] = out["right"]["ok"]
    out["failed"] = {n: not v["ok"] for n, v in out["variants"].items()}
    return out


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cell", default="mellum2_train_t8192")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--variant", nargs="*", default=[],
                    choices=sorted(VARIANTS) + ["all"])
    ap.add_argument("--control", action="store_true",
                    help="also hold the reference in a lower precision "
                         "(glm_check.lower_precision) to the limits: it "
                         "must fail")
    args = ap.parse_args(argv)
    variants = list(SEPARABLE) if "all" in args.variant else args.variant
    out = check(args.cell, args.seed, variants, control=args.control)
    print(json.dumps(out), flush=True)
    return 0 if out["ok"] and all(out["failed"].values()) else 1


if __name__ == "__main__":
    sys.exit(main())
