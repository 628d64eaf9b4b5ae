"""The builder's check of the ``laguna`` cell against its plain
reference at the PUBLISHED widths, outside any timed window (guide
``model-configs`` section 3, point 3; after ``tools/mellum_check.py``):

    python3 -m benchmark.tools.laguna_check [--cell laguna_s21_train_t8192]
        [--seed n] [--variant NAME ... | --variant all] [--control]

One batch of the cell (1 x 8192 tokens) goes through the model's own
train step — built from the cell's configuration with plain SGD at a
power-of-two rate in place of Adam, so that ``(before - after) / rate``
IS the step's gradient — and through ``reference/laguna_moe.py`` in
float32 at ``highest`` precision, on the same weights, with the same
held range (8 of 256 experts), vocabulary slice, layer kinds, head
counts, window and rotary tables.  The reference runs first, inside
the sequence a layer call at a time (``block=jax.checkpoint``),
attention a head at a time; then the program, and after it every
``--variant``, each a build of the PROGRAM that is wrong on purpose
while the reference stays right.  Held, each against a written limit:

- the step's loss (cross-entropy + 0.001 x the balance loss);
- every leaf's gradient, by the norm of the difference over the
  reference's norm, the gates' ``w_attn_gate`` among them; the
  routers' comes from the balance loss alone (a share by itself cuts
  the gates' gradient, ``parallel/moe.py``), in the reference as in
  the program;
- the routing counters: every expert's picks in every expert layer
  over ALL 256, by the largest difference over the mean load and by
  the sum of the differences over the picks.

The variants (``VARIANTS``), the seven the issue names: ``no_gate``
(attention's output goes to ``wo`` ungated), ``full_rotates_whole_head``
(the full layers rotate all 128 channels of a head),
``tables_swapped`` (each kind rotates by the other's entry),
``no_route_scale`` (the picked gates without the 2.5),
``no_shared_expert``, ``no_attention_factor`` (YaRN's frequencies
without its factor on cos and sin) and ``bf16_statistics`` (the
router's product, softmax and top-k and every RMSNorm's statistic in
bf16 where the file says float32).  ``--variant all`` runs them all.
A variant with fewer leaves (no gate, no shared expert) starts from
the right weights less those leaves and is held on the leaves it has.
The last line is a JSON object with every number but the single
leaves', ``ok`` of the right program and ``failed`` of each variant; the exit code is 0 when the
right program passed and every variant asked for failed.

``--control`` puts the REFERENCE ITSELF, computed in the nearest
precision below the cell's bf16 (``glm_check.lower_precision``: 3
mantissa bits), in the program's place: it has to fail a limit as a
wrong program does, or the limits would pass any arithmetic.

The limits (``LOSS_RTOL``, ``GRAD_RTOL``, ``GRAD_RTOL_ROUTED``,
``COUNT_RTOL``, ``COUNT_MEAN_RTOL`` below) each lie between two
readings on the chip; PERF.md section 6 (PR 50) has them all.
``bf16_statistics`` fails by the last alone: a first step's gradients
at 320 rows an expert cannot tell it from the right program.
"""

from __future__ import annotations

import argparse
import contextlib
import importlib
import json
import sys

from .glm_check import CONTROL, _patched, _rel0, _routed, lower_precision
from .olmoe_check import _flat

# Each limit lies between two readings on the chip (my chip runs A,
# C and D, PR 50, seeds 2147489711, -720, -721, -723; PERF.md section 6 has
# every reading): the largest the right program read over the four
# seeds and what the REFERENCE ITSELF read at 3 mantissa bits
# (``--control``), with room on both sides; the least a wrong program
# read is given too.  The readings hardly move with the seed (a
# program's worst leaf within 5 % of itself), so a limit a third above
# the right program's is room.
#: as ``drivers/train.py``'s: at initialisation the loss is ln(V)
#: whatever the architecture, which is why the loss alone proves
#: little.  Right 6.5e-6 to 1.4e-5 (the cell's seven runs up to
#: 6.8e-5); the control 3.0e-5 to 3.5e-4 (it may pass THIS limit);
#: the wrong ones 4.3e-6 (``bf16_statistics``) to 3.2e-3.
LOSS_RTOL = 2e-4
#: of the worst leaf's norm among the leaves no routed pick feeds
#: directly (the gates' and the routers' among them).  Right 0.102 to
#: 0.111 (``layers.4.wq`` / ``wk``: the last full layer; its gates
#: 0.078 to 0.086, its routers 0.031 to 0.035); the control 0.835 to
#: 0.841; ``bf16_statistics`` 0.118 to 0.130 (it passes this one),
#: ``no_route_scale`` 0.342 to 0.371, the five others 2.3 to 63.
GRAD_RTOL = 0.2
#: the held experts: 320 rows an expert, and bf16 flips some of the
#: top-10's near-ties among 256 scores, which moves a row into or out
#: of the held range, so the right program reads 0.317 to 0.394 where
#: Mellum's 2048 rows an expert read 0.073; the control 0.771 to
#: 0.790; ``no_route_scale`` 0.649 to 0.675, the five others 1.2 to
#: 1.6; ``bf16_statistics`` 0.216 to 0.305 (UNDER the right program's:
#: this number is the flips' noise, not its arithmetic).
GRAD_RTOL_ROUTED = 0.55
#: of the mean load (320 picks an expert and layer), the worst expert.
#: Right 0.041 to 0.059; the control 0.209 to 0.325;
#: ``bf16_statistics`` 0.062 to 0.084 and ``no_route_scale`` 0.078 to
#: 0.100 pass it, the five others 1.2 to 14.8.
COUNT_RTOL = 0.12
#: the picks that differ over ALL experts and layers, as a share of
#: the picks: the one number here that tells bf16 statistics from
#: float32 ones on the chip.  Right 0.0101, 0.0101 and 0.0101 (three
#: seeds); ``bf16_statistics`` 0.0129, 0.0130 and 0.0135,
#: ``no_route_scale`` 0.0155 to 0.0162, the control 0.0421 to 0.0456.  A sum over 327 680
#: picks, so a program's reading moves by under a hundredth of itself
#: with the seed: the limit lies an eighth above the right program's
#: and a ninth under the nearest wrong one's, thin by the numbers and
#: wide by their scatter.
COUNT_MEAN_RTOL = 0.0115
SGD_RATE = 2.0 ** 10


def _with(kind, **over):
    """``rope_parameters`` with one kind's entry changed."""
    def change(cfg):
        tables = dict(cfg["rope_parameters"])
        tables[kind] = dict(tables[kind], **over)
        return {"rope_parameters": tables}
    return change


def _swapped(cfg):
    tables = cfg["rope_parameters"]
    return {"rope_parameters": {
        "full_attention": tables["sliding_attention"],
        "sliding_attention": tables["full_attention"]}}


def _bf16_router(real):
    def router_topk(x2, w_router, top_k, renormalize=True, *,
                    scoring="softmax", select_bias=None, scale=1.0):
        import jax
        import jax.numpy as jnp

        assert scoring == "softmax" and select_bias is None
        bf16, f32 = jnp.bfloat16, jnp.float32
        logits = x2.astype(bf16) @ w_router.astype(bf16)
        probs = jax.nn.softmax(logits, axis=-1)
        gates, eidx = jax.lax.top_k(probs, top_k)
        if renormalize:
            gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
        return ((gates * scale).astype(f32), eidx, probs.astype(f32),
                logits.astype(f32))
    return router_topk


def _bf16_norm(real):
    def rms_norm(x, w, eps=1e-5, sharded_width=None, axes=-1):
        import jax
        import jax.numpy as jnp

        assert sharded_width is None
        ms = jnp.mean(x * x, axis=axes, keepdims=True)     # in x's dtype
        return x * jax.lax.rsqrt(ms + eps) * w.astype(x.dtype)
    return rms_norm


#: variant -> (what it changes of the program's configuration,
#: the (module, attribute, wrapper)s it patches in the program)
VARIANTS = {
    "no_gate": (lambda cfg: {"attention_gate": None}, ()),
    "full_rotates_whole_head": (
        _with("full_attention", partial_rotary_factor=1), ()),
    "tables_swapped": (_swapped, ()),
    "no_route_scale": (lambda cfg: {"moe_route_scale": 1.0}, ()),
    "no_shared_expert": (lambda cfg: {"moe_shared_experts": 0}, ()),
    "no_attention_factor": (
        _with("full_attention", attention_factor=1.0), ()),
    "bf16_statistics": (None, (
        ("theanompi_tpu.parallel.moe", "router_topk", _bf16_router),
        ("theanompi_tpu.models.llama", "rms_norm", _bf16_norm),
    )),
}


def _fitted(params, specs):
    """``params`` cut to the leaves ``specs`` names (a build without
    the gate or the shared expert holds fewer)."""
    if isinstance(specs, dict):
        return {k: _fitted(params[k], v) for k, v in specs.items()}
    if isinstance(specs, (list, tuple)):
        return [_fitted(p, s) for p, s in zip(params, specs, strict=True)]
    return params


def _program_step(config, cfg, patches, p0, batch):
    """(loss, gradients, pick counts [L_routed, E], gate counters) of
    one SGD step of the program built from ``cfg``."""
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
        start = _fitted(p0, model.param_specs())
        model.params = jax.device_put(start, model._shardings(model._specs))
        p1, _, _, loss, _, routing, *gate = model._train_step(
            model.params, model.opt_state, model.ef_state,
            *model.put_batch(batch), jnp.float32(SGD_RATE))
        loss = float(loss)
    grads = jax.tree.map(lambda a, b: (a - np.asarray(b)) / SGD_RATE,
                         start, jax.device_get(p1))
    picks = batch[0].size * int(cfg["moe_top_k"])
    counts = np.rint(np.asarray(routing, np.float64)[:, :-1] * picks)
    gate = np.asarray(gate[0], np.float64).tolist() if gate else None
    model.params = p1 = None
    jax.clear_caches()
    return loss, grads, counts, gate


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
                return ref._sequence(
                    p, ids, tgt, dict(kw, block=jax.checkpoint))

        n = x.size
        e = int(cfg["n_experts"])
        # pass 1: the batch's pick fractions (no gradient flows there)
        first = jax.jit(parts)
        counts = sum(np.asarray(first(p0, ids, tgt)[1], np.float64)
                     for ids, tgt in zip(x, y))
        f = jnp.asarray(counts / (n * kw["top_k"]), jnp.float32)

        def seq_loss(p, ids, tgt):
            ce, _, ssums = parts(p, ids, tgt)
            lb = jnp.mean(e * jnp.sum(f * ssums / n, axis=-1))
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
        return loss, grads, counts, None

    ref_loss, ref_grads, ref_counts, _ = reference_step()
    ref_flat = _flat(ref_grads)

    def program_step(name):
        if name == CONTROL:
            with lower_precision(ref):
                return reference_step()
        change, patches = VARIANTS[name] if name else (None, ())
        over = change(cfg) if change else {}
        return _program_step(config, dict(cfg, **over), patches, p0, batch)

    def held_to_reference(name):
        loss, grads, counts, gate = program_step(name)
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
            "grad_rel_gate": max(
                (v for k, v in grad_rel.items() if "w_attn_gate" in k),
                default=None),
            "count_rel_worst": float(
                np.max(np.abs(counts - ref_counts)) / ref_counts.mean()),
            "count_rel_mean": float(
                np.abs(counts - ref_counts).sum() / ref_counts.sum()),
            "picks_an_expert_max_over_mean": float(
                counts.max() / counts.mean()),
            "rows_held": counts[:, :held].sum(axis=1).tolist(),
            "attn_gate_open": gate,
        }
        # (a NaN is no pass: every comparison with it is False)
        got["ok"] = bool(
            got["loss_rel"] <= LOSS_RTOL
            and got["grad_rel_worst"] <= GRAD_RTOL
            and got["grad_rel_worst_routed"] <= GRAD_RTOL_ROUTED
            and got["count_rel_worst"] <= COUNT_RTOL
            and got["count_rel_mean"] <= COUNT_MEAN_RTOL
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
                   "count_rel_mean": COUNT_MEAN_RTOL},
        "right": held_to_reference(None),
        "variants": {name: held_to_reference(name)
                     for name in [*variants, *([CONTROL] if control else [])]},
    }
    out["ok"] = out["right"]["ok"]
    out["failed"] = {n: not v["ok"] for n, v in out["variants"].items()}
    return out


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cell", default="laguna_s21_train_t8192")
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
