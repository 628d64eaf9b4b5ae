"""The builder's check of the ``nemotron3`` cell against its plain
reference at the PUBLISHED widths, outside any timed window (guide
``model-configs`` section 3, point 3; after ``tools/laguna_check.py``
and ``tools/glm_check.py``):

    python3 -m benchmark.tools.nemotron_check [--cell NAME] [--seed n]
        [--variant NAME ... | --variant all] [--control]

One batch of the cell (2 x 8192 tokens) goes through the model's own
train step — built from the cell's configuration with plain SGD at a
power-of-two rate in place of Adam, so that ``(before - after) / rate``
IS the step's gradient — and through ``reference/nemotron_h.py`` in
float32 at ``highest`` precision, on the same weights, with the same
shares (8 of 512 experts, 16 of 128 state heads in one of 8 groups, 4
of 32 query heads over one of 2 key/value heads, the vocabulary's
slice), the same 11-block pattern and the same selection bias (a
random one, ``BIAS_STD``: at zeros a bias inside the gates or left out
of the selection would not show).  The reference runs first, a
sequence at a time and inside it a block call at a time
(``block=jax.checkpoint``), the recurrence a token at a time; then the
program, and after it every ``--variant``, each a build of the PROGRAM
that is wrong on purpose while the reference stays right.  Held, each
against a written limit:

- the step's loss (the cross-entropy: no balance loss);
- every leaf's gradient, by the norm of the difference over the
  reference's norm; a router's is exactly 0 on both sides (a share by
  itself holds its routers), so what is held there is the norm of what
  the program gives;
- the routing counters: every expert's picks in every expert block
  over ALL 512, by the largest difference over the mean load and by
  the sum of the differences over the picks;
- the selection bias after the step: the share of its entries that
  moved as the reference's moved (``rate`` toward balance by the sign).

The variants (``VARIANTS``), the six the issue names:
``bf16_statistics`` (the router's product, sigmoid and top-k and every
RMSNorm's statistic in bf16 where the file says float32),
``relu_not_squared`` (the experts' ``relu(.)`` without its square),
``silu_for_relu2`` (a SiLU in ``relu2``'s place), ``rope_on_attention``
(q and k rotated), ``router_reads_latent`` (the router reads the
1024-wide latent, through its leaf's first 1024 rows, as
``moe_ffn`` routed and computed on ONE input before this cell) and
``norm_before_gate`` (the mixer's norm before its gate, ``rmsnorm(y)
silu(z)``).  ``--variant all`` runs them all.  The last line is a JSON
object with every number but the single leaves', ``ok`` of the right
program and ``failed`` of each variant; the exit code is 0 when the
right program passed and every variant asked for failed.

``--control`` puts the REFERENCE ITSELF, computed in the nearest
precision below the cell's bf16 (``glm_check.lower_precision``: 3
mantissa bits), in the program's place: it has to fail a limit as a
wrong program does, or the limits would pass any arithmetic.

The limits below each lie between two readings on the chip; PERF.md
section 6 (PR 55) has them all.
"""

from __future__ import annotations

import argparse
import contextlib
import importlib
import json
import sys

from .glm_check import CONTROL, _patched, _rel0, _routed, lower_precision
from .laguna_check import _bf16_norm
from .olmoe_check import _flat

# Each limit lies between two readings on the chip (my chip runs n1
# and n2, PR 55, seeds 2147489811, -821 and -822: the right program and
# the control on all three, the six variants on the first; PERF.md
# section 6 has every reading): the largest the right program read
# over the seeds and the least the REFERENCE ITSELF read at 3 mantissa
# bits (``--control``), near their geometric mean; the least a wrong
# program read is given too.
#: as ``drivers/train.py``'s: at initialisation the loss is ln(V)
#: whatever the architecture, which is why the loss alone proves
#: little.  Right 6.5e-6 to 9.1e-6 (the cell's eight runs 4.1e-6 to
#: 2.8e-5); the control 9.7e-6 to 1.9e-4 and every wrong program
#: (1.6e-5 to 1.7e-4) pass THIS limit.
LOSS_RTOL = 2e-4
#: of the worst leaf's norm among the leaves no routed pick feeds
#: directly.  The worst is a latent projection (``w_lat_down`` /
#: ``w_lat_up`` of an expert block whose held experts got few rows):
#: its gradient is the held experts' rows' alone, so it carries their
#: flipped picks.  Right 0.159, 0.194, 0.225 (the mixers' worst 0.055
#: to 0.116); the control 0.526, 0.608, 0.668; ``bf16_statistics``
#: 0.196 (it passes this one), ``norm_before_gate`` 0.760,
#: ``rope_on_attention`` 1.41, the three others 1.6 to 2.8.  A
#: router's reads 0 on both sides.
GRAD_RTOL = 0.34
#: the held experts (704 rows an expert at balance; bf16 flips some of
#: the top-22's near-ties among 512 scores, which moves a row into or
#: out of the held range).  Right 0.159, 0.194, 0.223; the control
#: 0.525, 0.608, 0.667; ``bf16_statistics`` 0.197 and
#: ``rope_on_attention`` 0.237 pass it, the four others 0.76 to 1.6.
GRAD_RTOL_ROUTED = 0.34
#: of the mean load (704 picks an expert and block), the worst expert.
#: Right 0.047 to 0.055; ``bf16_statistics`` 0.176, the control 0.578
#: to 0.884, the four others 1.9 to 13.5; ``rope_on_attention`` 0.108.
COUNT_RTOL = 0.10
#: the picks that differ over ALL experts and blocks, as a share of the
#: picks.  Right 0.0036 to 0.0037; ``rope_on_attention`` 0.0054 passes
#: it; ``bf16_statistics`` 0.0125, the control 0.035 to 0.036, the four
#: others 0.075 to 0.74.
COUNT_MEAN_RTOL = 0.007
#: the share of the selection bias' entries (5 x 512) that moved as the
#: reference's: an entry differs only where an expert's count lies
#: within the flipped picks of its block's mean, so precision hardly
#: moves it (the control 0.9926 to 0.9969, ``bf16_statistics`` 0.9949
#: and ``rope_on_attention`` 0.9977 pass) and what it holds is the
#: RULE: the sign, the rate, the bias as state.  Right 0.9980 to
#: 0.9996; ``norm_before_gate`` 0.9855, the three others 0.83 to 0.966.
BIAS_SHARE = 0.99
SGD_RATE = 2.0 ** 10
BIAS_STD = 0.1


def _bf16_router(real):
    def router_topk(x2, w_router, top_k, renormalize=True, *,
                    scoring="softmax", select_bias=None, scale=1.0):
        import jax
        import jax.numpy as jnp

        assert scoring == "sigmoid"
        bf16, f32 = jnp.bfloat16, jnp.float32
        scores = jax.nn.sigmoid(x2.astype(bf16) @ w_router.astype(bf16))
        _, eidx = jax.lax.top_k(
            scores + jax.lax.stop_gradient(select_bias.astype(bf16)), top_k)
        gates = jnp.take_along_axis(scores, eidx, axis=-1)
        if renormalize:
            gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
        return ((gates * scale).astype(f32), eidx, scores.astype(f32),
                scores.astype(f32))
    return router_topk


def _relu(real):
    def relu2(u):
        import jax
        import jax.numpy as jnp

        return jax.nn.relu(u.astype(jnp.float32))
    return relu2


def _silu(real):
    def relu2(u):
        import jax
        import jax.numpy as jnp

        return jax.nn.silu(u.astype(jnp.float32))
    return relu2


def _router_on_latent(real):
    def moe_ffn(x, w_router, *experts, latent=None, **kw):
        z = x @ latent[0].astype(x.dtype)
        y, aux = real(z, w_router[:z.shape[-1]], *experts, **kw)
        return y @ latent[1].astype(x.dtype), aux
    return moe_ffn


def _norm_then_gate(real):
    def gated_rms_norm(y, z, w, eps=1e-5, n_groups=1):
        import jax
        import jax.numpy as jnp

        f32 = jnp.float32
        normed = real(y, jnp.full_like(z, 1e4), w, eps, n_groups)
        # (silu(1e4) = 1e4 for every channel: the statistic is y's)
        return (normed.astype(f32) * jax.nn.silu(z.astype(f32))
                ).astype(y.dtype)
    return gated_rms_norm


#: variant -> (configuration overrides, the (module, attribute,
#: wrapper)s it patches in the program)
VARIANTS = {
    "bf16_statistics": ({}, (
        ("theanompi_tpu.parallel.moe", "router_topk", _bf16_router),
        ("theanompi_tpu.models.llama", "rms_norm", _bf16_norm),
    )),
    "relu_not_squared": ({}, (
        ("theanompi_tpu.parallel.moe", "relu2", _relu),)),
    "silu_for_relu2": ({}, (
        ("theanompi_tpu.parallel.moe", "relu2", _silu),)),
    "rope_on_attention": ({"position_embedding_type": "rope"}, ()),
    "router_reads_latent": ({}, (
        ("theanompi_tpu.models.llama", "moe_ffn", _router_on_latent),)),
    "norm_before_gate": ({}, (
        ("theanompi_tpu.ops.ssd", "gated_rms_norm", _norm_then_gate),)),
}


def _program_step(config, cfg, patches, p0, bias0, batch):
    """(loss, gradients, pick counts [expert blocks, E], the bias
    after the step, the scans' counters) of one SGD step of the
    program built from ``cfg``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from theanompi_tpu.parallel import make_mesh

    cls = getattr(importlib.import_module(config["model"]["modelfile"]),
                  config["model"]["modelclass"])
    with contextlib.ExitStack() as stack:
        for patch in patches:
            stack.enter_context(_patched(patch))
        model = cls(cfg)
        model.build_model(n_replicas=1)
        model.compile_iter_fns(
            mesh=make_mesh(data=1, devices=jax.devices()[:1]))
        model.params = jax.device_put(p0, model._shardings(model._specs))
        model.net_state = jax.device_put(
            {"moe_bias": bias0}, model._shardings(model._state_specs[0]))
        p1, _, _, *rest = model._train_step(
            model.params, model.opt_state, model.ef_state,
            *model.put_batch(batch), jnp.float32(SGD_RATE),
            *model._state_args())
        loss, _, routing, _, *ssm = model._take_state(rest)
        loss = float(loss)
    grads = jax.tree.map(lambda a, b: (a - np.asarray(b)) / SGD_RATE,
                         p0, jax.device_get(p1))
    picks = batch[0].size * int(cfg["moe_top_k"])
    counts = np.rint(np.asarray(routing, np.float64)[:, :-1] * picks)
    bias = np.asarray(model.net_state["moe_bias"])
    # (a stack without an M block gives none)
    ssm = np.asarray(ssm[0], np.float64) if ssm else None
    model.params = model.net_state = p1 = rest = None
    jax.clear_caches()
    return loss, grads, counts, bias, ssm


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
    held = int(cfg["moe_experts_held"])
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
        """(loss, gradients, pick counts, the bias after the step) of
        the reference as the module stands, a sequence at a time."""
        one = jax.jit(jax.value_and_grad(
            lambda p, ids, tgt: ref.loss_and_counts(
                p, ids[None], tgt[None], select_bias=jnp.asarray(bias0),
                **kw, block=jax.checkpoint),
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
        return (loss, grads, counts,
                np.asarray(ref.bias_update(bias0, counts, rate)), None)

    ref_loss, ref_grads, ref_counts, ref_bias, _ = reference_step()
    ref_flat = _flat(ref_grads)

    def program_step(name):
        if name == CONTROL:
            with lower_precision(ref):
                return reference_step()
        over, patches = VARIANTS[name] if name else ({}, ())
        return _program_step(
            config, dict(cfg, **over), patches, p0, bias0, batch)

    def held_to_reference(name):
        loss, grads, counts, bias, ssm = program_step(name)
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
            "grad_rel_latent": max(
                v for k, v in grad_rel.items() if "w_lat_" in k),
            "grad_rel_mixer": max(
                v for k, v in grad_rel.items() if ".ssm_" in k),
            "count_rel_worst": float(
                np.max(np.abs(counts - ref_counts)) / ref_counts.mean()),
            "count_rel_mean": float(
                np.abs(counts - ref_counts).sum() / ref_counts.sum()),
            "picks_an_expert_max_over_mean": float(
                counts.max() / counts.mean()),
            "bias_moved_alike": float(np.mean(bias == ref_bias)),
            "rows_held": counts[:, :held].sum(axis=1).tolist(),
            "ssm_log_decay_min": None if ssm is None else ssm[:, 0].tolist(),
            "ssm_state_rms": None if ssm is None else ssm[:, 1].tolist(),
        }
        # (a NaN is no pass: every comparison with it is False)
        got["ok"] = bool(
            got["loss_rel"] <= LOSS_RTOL
            and got["grad_rel_worst"] <= GRAD_RTOL
            and got["grad_rel_worst_routed"] <= GRAD_RTOL_ROUTED
            and got["count_rel_worst"] <= COUNT_RTOL
            and got["count_rel_mean"] <= COUNT_MEAN_RTOL
            and got["bias_moved_alike"] >= BIAS_SHARE
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
                   "count_rel": COUNT_RTOL,
                   "count_rel_mean": COUNT_MEAN_RTOL,
                   "bias_moved_alike": BIAS_SHARE},
        "right": held_to_reference(None),
        "variants": {name: held_to_reference(name)
                     for name in [*variants, *([CONTROL] if control else [])]},
    }
    out["ok"] = out["right"]["ok"]
    out["failed"] = {n: not v["ok"] for n, v in out["variants"].items()}
    return out


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cell", default="nemotron3_super_train_t8192")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--variant", nargs="*", default=[],
                    choices=sorted(VARIANTS) + ["all"])
    ap.add_argument("--control", action="store_true",
                    help="also hold the reference in a lower precision "
                         "(glm_check.lower_precision) to the limits: it "
                         "must fail")
    args = ap.parse_args(argv)
    variants = list(VARIANTS) if "all" in args.variant else args.variant
    out = check(args.cell, args.seed, variants, control=args.control)
    # (the last line without each leaf's number: they are in the
    # variants' own lines' worst, and the tail of a chip call is short)
    for got in (out["right"], *out["variants"].values()):
        got.pop("grad_rel")
    print(json.dumps(out), flush=True)
    return 0 if out["ok"] and all(out["failed"].values()) else 1


if __name__ == "__main__":
    sys.exit(main())
