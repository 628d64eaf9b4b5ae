"""The builder's check of the ``glm4_moe_lite`` cell against its plain
reference at the PUBLISHED widths, outside any timed window (guide
``model-configs`` section 3, point 3; after ``tools/ouro_check.py``):

    python3 -m benchmark.tools.glm_check [--cell glm47flash_train_t8192]
        [--seed n] [--variant NAME ... | --variant all] [--control]

One batch of the cell (2 x 8192 tokens) goes through the model's own
train step — built from the cell's configuration with plain SGD at a
power-of-two rate in place of Adam, so that ``(before - after) / rate``
IS the step's gradient — and through ``reference/glm_moe_lite.py`` in
float32 at ``highest`` precision, on the same weights, with the same
held range (8 of 64 experts) and vocabulary slice.  The selection
bias starts from seeded values of about the scores' own scatter
(normal, 0.1), not from zero: a bias of zero cannot tell a program
that keeps it out of the gates from one that does not.  The
reference runs first, a sequence at a time and inside it a layer call
at a time (``block=jax.checkpoint``); then the program, and after it
every ``--variant``, each a build of the PROGRAM that is wrong on
purpose while the reference stays right.  Held, each against a
written limit:

- the step's loss (main + 0.3 x MTP);
- every leaf's gradient (the two latent norms, ``eh_proj``, the
  shared expert among them), by the norm of the difference over the
  reference's norm; the routers of a share by itself get none
  (``parallel/moe.py`` ``moe_ffn``), in the reference as in the
  program, and are held to exactly that;
- the routing counters: every expert's picks in every expert layer
  call over ALL 64, by the largest difference over the mean load;
- the selection bias after the step: the share of its entries that
  moved as the reference's rule moves them.

The variants: ``softmax`` (softmax scores in place of the sigmoid),
``no_scale`` (gates not times 1.8), ``bias_in_gates`` (the gates
taken from ``s + b``), ``k_rope_per_head`` (the rotary key differs
from head to head), ``no_shared`` (the shared expert's output left
out), ``mtp_unshifted`` (the MTP exit held to the NEXT token).  Each
must fail a limit; the last line is a JSON object with every number,
``ok`` of the right program and ``failed`` of each variant, and the
exit code is 0 when the right program passed and every variant asked
for failed.

``--control`` puts the REFERENCE ITSELF, computed in the nearest
precision below the cell's bf16, in the program's place
(``lower_precision``: every weight and every normalised activation
rounded to 3 mantissa bits, e4m3's, at bf16's range, going into the
products; float32 sums): it has to fail a limit as a wrong program
does, or the limits would pass any arithmetic.

The limits.  bf16 compute against a float32 reference is noisier
here than in the other cells: the router's top-4 is discontinuous,
bf16 activations flip some 3 % of the picks at a near-tie of the 4th
and 5th score, each flip moves a whole row with a renormalised gate
near 0.45 into or out of the held range, and the flips of earlier
layers feed later ones (in float32 on the CPU the same step reads
2.4e-6 on every leaf; the held experts of a layer call that got few
rows read worst).  So the gradients have two limits.  Each limit
lies between two readings on the chip (my chip runs r2, r4 and r5,
PR 37, seeds 2147489401, -02 and -04, the tree with the share's
routers held, at the bias scatter this file has, 0.3): the largest
the right program read, and what the REFERENCE ITSELF read at 3
mantissa bits (``--control``, r2 and r5); the least a wrong program
read is given too, and the limit lies under it:

- ``LOSS_RTOL`` 2e-4, as ``drivers/train.py``'s (right 2.0e-6 to
  1.3e-5; the control 1.3e-5 and 2.5e-5; the wrong ones 1.3e-5 to
  6.2e-4: at initialisation the loss is 1.3 ln(V) whatever the
  architecture or the precision, which is why the loss alone proves
  little).
- ``GRAD_RTOL`` 0.10 of the worst leaf's norm among the leaves no
  routed pick feeds directly, the routers among them (a share's get
  no gradient: exactly 0 on both sides) (right 0.068, 0.080, 0.088;
  the control 0.406 and 0.412; bias in the gates 0.116, 0.130, 0.187,
  no 1.8 0.286, softmax 0.631, rotary key per head 1.21, MTP
  unshifted 1.55, no shared expert 5.32).
- ``GRAD_RTOL_ROUTED`` 0.25 for the held experts (right 0.168, 0.215,
  0.220; the control 0.619 and 0.670; bias in the gates 0.276, 0.346,
  0.404, no 1.8 0.551, rotary key per head 0.970, softmax 1.25, MTP
  unshifted 1.47, no shared expert 1.96).
- ``COUNT_RTOL`` 0.07 of the mean load (right 0.027 to 0.031; the
  control 0.604 and 0.649; no 1.8 0.113, rotary key per head 0.173,
  no shared expert 6.37, softmax 8.65; a bias in the gates and
  unshifted MTP labels leave the picks alone).
- ``BIAS_SHARE`` 0.98 (right 1.0, 1.0 and 0.997: 319 of 320 entries,
  one expert a flipped pick away from its layer's mean; no shared
  expert 0.95, softmax 0).

A bias inside the gates is the nearest fault, and the room is thin:
the right program's worst reading and that fault's least lie 14 % and
16 % from ``GRAD_RTOL`` (14 % and 10 % from ``GRAD_RTOL_ROUTED``); on
each seed the fault reads 1.6 to 2.1 times the right program, both
moving with that seed's flipped picks.  With the routers held it
shows only in the held experts' rows (before, the routers' gradient
gave it away at 0.62).  A bias scatter of 0.6 parts the two further
(0.280 and 0.474 against 0.096 and 0.182, seed 2147489403, r4) but
sends one layer call's held experts no row at all; PERF.md, section
7, says what would make the check independent of the flips.
"""

from __future__ import annotations

import argparse
import contextlib
import importlib
import json
import sys

from .olmoe_check import _flat, _rel

LOSS_RTOL = 2e-4
GRAD_RTOL = 0.10            # leaves no routed pick feeds directly
GRAD_RTOL_ROUTED = 0.25     # the held experts
COUNT_RTOL = 0.07
BIAS_SHARE = 0.98
SGD_RATE = 2.0 ** 10
BIAS_STD = 0.3


def _routed(leaf: str) -> bool:
    return ".we_" in leaf


def _rel0(got, want) -> float:
    """``_rel``; against a reference that is exactly zero (a held
    router's gradient) the norm of what was got."""
    import numpy as np

    if not np.any(want):
        return float(np.linalg.norm(np.asarray(got, np.float64)))
    return _rel(got, want)


# -- the program, wrong on purpose -------------------------------------------


def _bias_in_gates(real):
    def router_topk(x2, w_router, top_k, renormalize=True, *,
                    scoring="softmax", select_bias=None, scale=1.0):
        import jax
        import jax.numpy as jnp
        from jax import lax

        logits = x2.astype(jnp.float32) @ w_router.astype(jnp.float32)
        scores = jax.nn.sigmoid(logits)
        gates, eidx = lax.top_k(
            scores + lax.stop_gradient(select_bias), top_k)
        gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + 1e-20)
        return gates * scale, eidx, scores, logits
    return router_topk


def _k_rope_per_head(real):
    def _mla_qkv(self, p, xn, pos):
        import jax.numpy as jnp

        q, k, v = real(self, p, xn, pos)
        nope = self.qk_nope_head_dim
        turned = jnp.stack([
            jnp.roll(k[:, h, :, nope:], 2 * h, axis=-1)
            for h in range(k.shape[1])
        ], axis=1)
        return q, jnp.concatenate([k[..., :nope], turned], axis=-1), v
    return _mla_qkv


def _no_shared(real):
    def shared_expert(x, *_):
        import jax.numpy as jnp

        return jnp.zeros_like(x)
    return shared_expert


#: variant -> (configuration overrides, (module, attribute, wrapper))
VARIANTS = {
    "softmax": ({"moe_scoring": "softmax"}, None),
    "no_scale": ({"moe_route_scale": 1.0}, None),
    "bias_in_gates": (
        {}, ("theanompi_tpu.parallel.moe", "router_topk", _bias_in_gates)),
    "k_rope_per_head": (
        {}, ("theanompi_tpu.models.llama", "Llama._mla_qkv",
             _k_rope_per_head)),
    "no_shared": (
        {}, ("theanompi_tpu.models.llama", "shared_expert", _no_shared)),
    "mtp_unshifted": ({"_mtp_unshifted": True}, None),
}


@contextlib.contextmanager
def _patched(patch):
    if patch is None:
        yield
        return
    module, path, wrapper = patch
    owner = importlib.import_module(module)
    *parents, name = path.split(".")
    for part in parents:
        owner = getattr(owner, part)
    real = getattr(owner, name)
    setattr(owner, name, wrapper(real))
    try:
        yield
    finally:
        setattr(owner, name, real)


def _model_class(config, unshifted: bool):
    base = getattr(importlib.import_module(config["model"]["modelfile"]),
                   config["model"]["modelclass"])
    if not unshifted:
        return base

    class Unshifted(base):
        """The MTP exit held to the NEXT token, as the main exit is:
        the labels ``_mtp_loss`` moves one place on are moved one
        place back first (the main exit's labels stay right: the
        loss's main part is taken from the right call)."""

        def _mtp_loss(self, params, exits, y, head_xent=None):
            import jax.numpy as jnp

            back = jnp.concatenate([y[:, :1], y[:, :-1]], axis=1)
            wrong, _ = super()._mtp_loss(params, exits, back, head_xent)
            # ... which moved the main exit's labels too: its part is
            # exchanged for the right one (a zero MTP exit reads
            # ln(V) whatever its labels, and cancels)
            no_mtp = jnp.concatenate([exits[:1], jnp.zeros_like(exits[1:])])
            main_back, _ = super()._mtp_loss(params, no_mtp, back, head_xent)
            main, err = super()._mtp_loss(params, no_mtp, y, head_xent)
            return wrong - main_back + main, err

    return Unshifted


# -- the reference itself, in a lower precision ------------------------------

CONTROL = "reference_in_lower_precision"


@contextlib.contextmanager
def lower_precision(ref, mantissa_bits: int = 3):
    """``ref`` computing in the nearest precision below bf16's 7
    mantissa bits: every weight (``_f32``) and every normalised
    activation (``_rmsnorm``) is rounded to ``mantissa_bits`` (3:
    float8 e4m3's) at bf16's exponent range on its way into a product;
    the sums stay float32 and the backward pass reads the rounded
    values through unrounded (what a low-precision kernel with a wide
    accumulator does)."""
    import jax

    def rounded(f):
        def g(*args):
            a = f(*args)
            return a + jax.lax.stop_gradient(
                jax.lax.reduce_precision(a, 8, mantissa_bits) - a)
        return g

    real = ref._f32, ref._rmsnorm
    ref._f32, ref._rmsnorm = rounded(ref._f32), rounded(ref._rmsnorm)
    try:
        yield
    finally:
        ref._f32, ref._rmsnorm = real


# -- one program against the reference ----------------------------------------


def _program_step(config, cfg, over, patch, p0, bias0, batch):
    """(loss, gradients, pick counts [L, E], bias after the step) of
    one SGD step of the program built from ``cfg`` + ``over``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from theanompi_tpu.parallel import make_mesh

    over = dict(over)
    cls = _model_class(config, over.pop("_mtp_unshifted", False))
    with _patched(patch):
        model = cls(dict(cfg, **over))
        model.build_model(n_replicas=1)
        model.compile_iter_fns(
            mesh=make_mesh(data=1, devices=jax.devices()[:1]))
        model.params = jax.device_put(p0, model._shardings(model._specs))
        if model._state_specs:      # (a softmax router has no bias)
            model.net_state = jax.device_put(
                {"moe_bias": bias0},
                model._shardings(model._state_specs[0]))
        p1, _, _, *rest = model._train_step(
            model.params, model.opt_state, model.ef_state,
            *model.put_batch(batch), jnp.float32(SGD_RATE),
            *model._state_args())
        loss, _, routing, *_ = model._take_state(rest)
        loss = float(loss)
    grads = jax.tree.map(lambda a, b: (a - np.asarray(b)) / SGD_RATE,
                         p0, jax.device_get(p1))
    picks = batch[0].size * int(cfg["moe_top_k"])
    counts = np.rint(np.asarray(routing, np.float64)[:, :-1] * picks)
    bias = (np.asarray(model.net_state["moe_bias"])
            if model.net_state else bias0)
    model.params = model.net_state = p1 = rest = None
    jax.clear_caches()
    return loss, grads, counts, bias


def check(cell_name: str, seed: int, variants: list[str],
          rehearsal: bool = False, control: bool = False) -> dict:
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
               optimizer="sgd", device_data_cache=False)
    ref_spec = config["reference"]
    ref = importlib.import_module(
        f"{__package__.rsplit('.', 1)[0]}.reference.{ref_spec['module']}")
    kw = ref_spec["kwargs"]
    rate = float(cfg["moe_bias_rate"])

    # the weights, the batch and the bias every build starts from
    model = getattr(importlib.import_module(config["model"]["modelfile"]),
                    config["model"]["modelclass"])(cfg)
    model.build_model(n_replicas=1)
    model.compile_iter_fns(mesh=make_mesh(data=1, devices=jax.devices()[:1]))
    p0 = jax.tree.map(np.asarray, jax.device_get(model.params))
    model.data.shuffle(0)
    batch = tuple(np.asarray(a) for a in model.data.train_batch(0))
    bias0 = (BIAS_STD * np.random.default_rng(seed).standard_normal(
        (model.moe_calls, model.n_experts))).astype(np.float32)
    model.params = model.opt_state = model.net_state = None
    del model
    jax.clear_caches()

    x, y = batch

    def reference_step():
        """(loss, gradients, pick counts) of the reference as the
        module stands, a sequence at a time."""
        one = jax.jit(jax.value_and_grad(
            lambda p, ids, tgt: ref.loss_and_counts(
                p, ids[None], tgt[None], bias=jnp.asarray(bias0), **kw,
                block=jax.checkpoint),
            has_aux=True))
        loss, grads, counts = 0.0, None, 0.0
        for ids, tgt in zip(x, y):
            (l, c), g = one(p0, ids, tgt)
            g = jax.tree.map(lambda a: np.asarray(a) / len(x), g)
            grads = g if grads is None else jax.tree.map(np.add, grads, g)
            loss += float(l) / len(x)
            counts = counts + np.asarray(c, np.float64)
        del one
        jax.clear_caches()
        return loss, grads, counts

    def bias_after(counts):
        return np.asarray(ref.bias_update(bias0, counts, rate))

    ref_loss, ref_grads, ref_counts = reference_step()
    ref_bias = bias_after(ref_counts)
    ref_flat = _flat(ref_grads)

    def program_step(name):
        if name == CONTROL:
            with lower_precision(ref):
                loss, grads, counts = reference_step()
            return loss, grads, counts, bias_after(counts)
        over, patch = VARIANTS.get(name, ({}, None))
        return _program_step(config, cfg, over, patch, p0, bias0, batch)

    def held_to_reference(name):
        loss, grads, counts, bias = program_step(name)
        flat = _flat(grads)
        grad_rel = {k: _rel0(flat[k], ref_flat[k]) for k in flat}
        got = {
            "loss": loss,
            "loss_rel": abs(loss - ref_loss) / abs(ref_loss),
            "grad_rel": grad_rel,
            "grad_rel_worst": max(
                v for k, v in grad_rel.items() if not _routed(k)),
            "grad_rel_worst_routed": max(
                v for k, v in grad_rel.items() if _routed(k)),
            "count_rel_worst": float(
                np.max(np.abs(counts - ref_counts)) / ref_counts.mean()),
            "bias_moved_alike": float(np.mean(bias == ref_bias)),
            "rows_held": counts[:, :int(cfg["moe_experts_held"])]
            .sum(axis=1).tolist(),
        }
        got["ok"] = bool(
            got["loss_rel"] <= LOSS_RTOL
            and got["grad_rel_worst"] <= GRAD_RTOL
            and got["grad_rel_worst_routed"] <= GRAD_RTOL_ROUTED
            and got["count_rel_worst"] <= COUNT_RTOL
            and got["bias_moved_alike"] >= BIAS_SHARE
        )
        print(json.dumps({"variant": name, **{
            k: v for k, v in got.items() if k != "grad_rel"}}), flush=True)
        return got

    out = {
        "cell": cell_name, "seed": seed,
        "device": {"platform": jax.devices()[0].platform,
                   "kind": jax.devices()[0].device_kind},
        "tokens": int(x.size), "reference_loss": ref_loss,
        "reference_rows_held": ref_counts[
            :, :int(cfg["moe_experts_held"])].sum(axis=1).tolist(),
        "limits": {"loss_rel": LOSS_RTOL, "grad_rel": GRAD_RTOL,
                   "grad_rel_routed": GRAD_RTOL_ROUTED,
                   "count_rel": COUNT_RTOL, "bias_moved_alike": BIAS_SHARE},
        "right": held_to_reference(None),
        "variants": {name: held_to_reference(name)
                     for name in [*variants, *([CONTROL] if control else [])]},
    }
    out["ok"] = out["right"]["ok"]
    out["failed"] = {n: not v["ok"] for n, v in out["variants"].items()}
    return out


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cell", default="glm47flash_train_t8192")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--variant", nargs="*", default=[],
                    choices=sorted(VARIANTS) + ["all"])
    ap.add_argument("--control", action="store_true",
                    help="also hold the reference in a lower precision "
                         "(lower_precision) to the limits: it must fail")
    args = ap.parse_args(argv)
    variants = sorted(VARIANTS) if "all" in args.variant else args.variant
    out = check(args.cell, args.seed, variants, control=args.control)
    print(json.dumps(out), flush=True)
    return 0 if out["ok"] and all(out["failed"].values()) else 1


if __name__ == "__main__":
    sys.exit(main())
