"""The builder's check of the looped-decoder cell against its plain
reference at the PUBLISHED widths, outside any timed window (guide
``model-configs`` section 3, point 3; after ``tools/olmoe_check.py``):

    python3 -m benchmark.tools.ouro_check [--cell ouro_train_t4096] [--seed n]
        [--variant no_sandwich|theta_1e4|no_entropy]

One batch of the cell (2 x 4096 tokens) goes through the model's own
train step — built from the cell's configuration with plain SGD at a
power-of-two rate in place of Adam, so that ``(before - after) / rate``
IS the step's gradient, the sum over the four passes and all — and
through ``reference/ouro.py`` in float32 at ``highest`` precision, on
the same weights.  The reference's gradients are computed a sequence
at a time and, inside a sequence, a layer call and an exit at a time
(``block=jax.checkpoint``: the backward then holds one layer's
``[16, 4096, 4096]`` scores, or one exit's ``[4096, 49152]`` logits,
and not 32 and 4 of them).  Held, each against a written limit:

- the step's loss (the four exits' cross-entropies under the exit
  distribution, less ``beta`` times its entropy);
- every leaf's gradient, the gate's and the four norms' among them,
  by the norm of the difference over the reference's norm;
- the step's exit counters (mean ``q_t``, mean cross-entropy of each
  exit) against the reference's, by the largest relative difference.

``--variant`` builds the PROGRAM wrong on purpose (a plain pre-norm
block without the two output norms, RoPE at the repo's old theta of
1e4, the loss without its entropy term) while the reference stays
right: each must fail, which is what shows the limits are tight
enough.  The last line is a JSON object with every number and ``ok``;
the exit code is 0 when ``ok`` is what was expected.

The limits.  bf16 compute against a float32 reference, on the chip
at these widths (my chip run k3, PR 33, seed 2147483901; the same
step, four passes deep): the right program's loss differs by 1.2e-5,
its leaves' gradients by 0.0088-0.0281 of their norms (worst ``wk``
and ``wq``, least the gate's), its exit counters by up to 0.0112
(the gate reads bf16 exits).  Each limit lies between that and what
the three wrong programs read in the same call:

- ``LOSS_RTOL`` 2e-4, as ``drivers/train.py``'s (right 1.2e-5; no
  output norms 3.4e-4, no entropy term 1.0e-2; theta 1e4 reads
  1.3e-4 and passes it: at initialisation the loss is ln(V) whatever
  the rotation, which is why the loss alone proves little).
- ``GRAD_RTOL`` 0.08 of the worst leaf's norm (right 0.028; no
  entropy term 0.97, theta 1e4 1.09, no output norms 2.09).
- ``COUNTER_RTOL`` 0.018 (right 0.0112; theta 1e4 0.0293, no output
  norms 0.478; the entropy term does not move the counters).
"""

from __future__ import annotations

import argparse
import importlib
import json
import sys

from .olmoe_check import _flat, _rel

LOSS_RTOL = 2e-4
GRAD_RTOL = 0.08
COUNTER_RTOL = 0.018
SGD_RATE = 2.0 ** 10

VARIANTS = {
    "no_sandwich": {"sandwich_norm": False},
    "theta_1e4": {"rope_theta": 1e4},
    "no_entropy": {"exit_beta": 0.0},
}


def check(cell_name: str, seed: int, variant: str | None,
          rehearsal: bool = False) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ..drivers.train import program_config
    from ..run import load_cell

    from theanompi_tpu.parallel import make_mesh

    config = load_cell(cell_name)["config"]
    if rehearsal:
        config = dict(config, **config["rehearsal"])
    cfg = dict(program_config(config, seed=seed, n_replicas=1),
               optimizer="sgd", device_data_cache=False,
               **VARIANTS.get(variant, {}))
    ref_spec = config["reference"]
    ref = importlib.import_module(
        f"{__package__.rsplit('.', 1)[0]}.reference.{ref_spec['module']}")
    kw = ref_spec["kwargs"]

    model = getattr(importlib.import_module(config["model"]["modelfile"]),
                    config["model"]["modelclass"])(cfg)
    model.build_model(n_replicas=1)
    model.compile_iter_fns(mesh=make_mesh(data=1, devices=jax.devices()[:1]))
    p0 = jax.tree.map(np.asarray, jax.device_get(model.params))
    model.data.shuffle(0)
    x, y = (np.asarray(a) for a in model.data.train_batch(0))

    p1, _, _, loss, _, counters = model._train_step(
        model.params, model.opt_state, model.ef_state,
        *model.put_batch((x, y)), jnp.float32(SGD_RATE))
    loss = float(loss)
    counters = np.asarray(counters, np.float64)
    grads = jax.tree.map(lambda a, b: (a - np.asarray(b)) / SGD_RATE,
                         p0, jax.device_get(p1))
    model.params = p1 = None
    jax.clear_caches()

    # the reference, on the weights the reference's architecture has
    # (a variant without the output norms has no such leaves: ones)
    ref_params = p0
    if variant == "no_sandwich":
        ones = np.ones_like(p0["final_norm"])
        ref_params = dict(p0, layers=[
            dict(lp, attn_out_norm=ones, mlp_out_norm=ones)
            for lp in p0["layers"]
        ])

    @jax.jit
    def terms(p, ids, tgt):
        ref_kw = {k: v for k, v in kw.items() if k != "beta"}
        with jax.default_matmul_precision("highest"):
            q, xent = ref.sequence_terms(p, ids, tgt, **ref_kw)
        return jnp.mean(q, 1), jnp.mean(xent, 1)

    one = jax.jit(jax.value_and_grad(
        lambda p, ids, tgt: ref.loss(p, ids[None], tgt[None], **kw,
                                     block=jax.checkpoint)))
    ref_loss, ref_grads, ref_mass, ref_xent = 0.0, None, 0.0, 0.0
    for ids, tgt in zip(x, y):          # a sequence at a time
        l, g = one(ref_params, ids, tgt)
        g = jax.tree.map(lambda a: np.asarray(a) / len(x), g)
        ref_grads = g if ref_grads is None else jax.tree.map(
            np.add, ref_grads, g)
        ref_loss += float(l) / len(x)
        mass, xent = terms(ref_params, ids, tgt)
        ref_mass = ref_mass + np.asarray(mass, np.float64) / len(x)
        ref_xent = ref_xent + np.asarray(xent, np.float64) / len(x)

    r = len(ref_mass)
    flat, ref_flat = _flat(grads), _flat(ref_grads)
    grad_rel = {k: _rel(flat[k], ref_flat[k]) for k in flat}
    want = np.concatenate([ref_mass, ref_xent])
    out = {
        "cell": cell_name, "seed": seed, "variant": variant,
        "device": {"platform": jax.devices()[0].platform,
                   "kind": jax.devices()[0].device_kind},
        "tokens": int(x.size), "passes": r,
        "loss": loss, "reference_loss": ref_loss,
        "loss_rel": abs(loss - ref_loss) / abs(ref_loss),
        "grad_rel": grad_rel,
        "grad_rel_worst": max(grad_rel.values()),
        "exit_mass": counters[:r].tolist(),
        "reference_exit_mass": ref_mass.tolist(),
        "exit_loss": counters[r:2 * r].tolist(),
        "reference_exit_loss": ref_xent.tolist(),
        "counter_rel_worst": float(
            np.max(np.abs(counters[:2 * r] - want) / np.abs(want))),
        "mean_exit_step": float(counters[-1]),
        "limits": {"loss_rel": LOSS_RTOL, "grad_rel": GRAD_RTOL,
                   "counter_rel": COUNTER_RTOL},
    }
    out["ok"] = bool(
        out["loss_rel"] <= LOSS_RTOL
        and out["grad_rel_worst"] <= GRAD_RTOL
        and out["counter_rel_worst"] <= COUNTER_RTOL
    )
    return out


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cell", default="ouro_train_t4096")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--variant", choices=sorted(VARIANTS))
    args = ap.parse_args(argv)
    out = check(args.cell, args.seed, args.variant)
    print(json.dumps(out), flush=True)
    return 0 if out["ok"] == (args.variant is None) else 1


if __name__ == "__main__":
    sys.exit(main())
