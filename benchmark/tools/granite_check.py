"""The builder's check of the ``granite4h`` cell against its plain
reference at the PUBLISHED widths, outside any timed window (guide
``model-configs`` section 3, point 3; after ``tools/mellum_check.py``):

    python3 -m benchmark.tools.granite_check [--cell granite4h_micro_train_t8192]
        [--seed n] [--variant NAME ... | --variant all] [--control]

One batch of the cell (1 x 8192 tokens: 32 chunks of the scan) goes
through the model's own train step — built from the cell's
configuration with plain SGD at a power-of-two rate in place of Adam,
so that ``(before - after) / rate`` IS the step's gradient — and
through ``reference/granite_hybrid.py`` in float32 at ``highest``
precision, on the same weights, with the same ten layer kinds and
vocabulary slice: the recurrence a token at a time, every layer call
replayed in the backward (``block=jax.checkpoint``).  The reference
runs first; then the program, and after it every ``--variant``, each
a build of the PROGRAM that is wrong on purpose while the reference
stays right.  Held, each against a written limit:

- the step's loss;
- every leaf's gradient, by the norm of the difference over the
  reference's norm — the tied matrix's as ONE leaf (``embed``: the
  lookup's scatter-add plus the head's ``dW``);
- the logits of the first sequence, by the largest difference over
  the largest reference logit.

The variants (``VARIANTS``): ``no_state_carry`` (every chunk of the
scan starts from a zero state), ``conv_shifted`` (the convolution's
taps one place late: t-4..t-1), ``no_d_skip`` (``y`` without ``D
x``), ``norm_before_gate`` (``rmsnorm(y) * silu(z)``),
``dt_no_softplus`` (``dt + dt_bias`` as it is), ``rope_on_attention``
(q and k rotated), ``sm_scale_sqrt`` (the scores times ``1 /
sqrt(64)`` = 0.125 for 0.015625), ``no_residual_multiplier``,
``no_logits_scaling``, ``untied_head`` (a head leaf of its own, equal
to the embedding transposed at the start: the SAME forward, so the
same loss and logits to the bit; the embedding's gradient loses the
head's part) and ``pattern_shifted`` (the attention layer one place
early, the layers' weights following their kinds).  ``--variant all``
runs them all.  The last line is a JSON object with every number,
``ok`` of the right program and ``failed`` of each variant; the exit
code is 0 when the right program passed and every variant asked for
failed.

``--control`` puts the REFERENCE ITSELF, computed in the nearest
precision below the cell's bf16 (``glm_check.lower_precision``: 3
mantissa bits), in the program's place: it has to fail a limit as a
wrong program does, or the limits would pass any arithmetic.

The limits (``LOSS_RTOL``, ``GRAD_RTOL``, ``LOGITS_RTOL`` below) each
lie between two readings on the chip; PERF.md section 6 (PR 47) has
them all.
"""

from __future__ import annotations

import argparse
import importlib
import json
import sys

from .glm_check import CONTROL, _patched, _rel0, lower_precision
from .olmoe_check import _flat

# Each limit lies between two readings on the chip (my chip run F1,
# PR 47, seed 2147489611; PERF.md section 6 has every reading): the
# largest the right program read and what the REFERENCE ITSELF read at
# 3 mantissa bits (``--control``), with room on both sides; the least
# a wrong program read is given too.
#: as ``drivers/train.py``'s.  Right 4.9e-6 (7.1e-7 on a second seed);
#: the control 1.0e-5 (it passes THIS limit: at initialisation the loss
#: is ln(V) whatever the arithmetic); the wrong ones 1.0e-7 to 0.28,
#: seven of eleven inside.
LOSS_RTOL = 2e-4
#: of the worst leaf's norm.  Right 0.024 and 0.026 on two seeds (a
#: mamba layer's ``dt_bias``; the tied matrix 0.015; 0.031 with the
#: scan's first form); the control 0.30; the wrong ones from 0.48
#: (``untied_head``: the tied matrix alone) up.
GRAD_RTOL = 0.10
#: of the largest reference logit (1.88).  Right 0.0052 and 0.0054;
#: the control 0.055; ``rope_on_attention`` 0.0055 and ``untied_head``
#: 0.0052 pass it and fail by their gradients.
LOGITS_RTOL = 0.02
SGD_RATE = 2.0 ** 10


def _wrap_scan(change):
    """``ssd.ssd_scan`` with its arguments changed."""
    def wrapper(real):
        def ssd_scan(x, dt, A, B, C, D, chunk, **kw):
            return real(*change(x, dt, A, B, C, D), chunk, **kw)
        return ssd_scan
    return wrapper


def _no_d(x, dt, A, B, C, D):
    return x, dt, A, B, C, 0.0 * D


def _raw_dt(x, dt, A, B, C, D):
    import jax.numpy as jnp

    # softplus undone: the scan gets ``dt + dt_bias`` as it is
    return x, jnp.log(jnp.expm1(dt)), A, B, C, D


def _dead_carry(real):
    def _carried_states(states, total):
        import jax.numpy as jnp

        return jnp.zeros_like(states)
    return _carried_states


def _late_conv(real):
    def causal_conv_silu(x, w, b):
        import jax.numpy as jnp

        late = jnp.pad(x, ((0, 0), (1, 0), (0, 0)))[:, :-1]
        return real(late, w, b)
    return causal_conv_silu


def _gate_after(real):
    def gated_rms_norm(y, z, w, eps=1e-5, n_groups=1):
        import jax
        import jax.numpy as jnp

        f = y.astype(jnp.float32).reshape(*y.shape[:-1], n_groups, -1)
        f = f * jax.lax.rsqrt(jnp.mean(f * f, -1, keepdims=True) + eps)
        normed = f.reshape(y.shape) * w.astype(jnp.float32)
        return (normed * jax.nn.silu(z.astype(jnp.float32))).astype(y.dtype)
    return gated_rms_norm


_SSD = "theanompi_tpu.ops.ssd"


def _shifted(cfg):
    """The attention layer one place early."""
    return {"layer_types": list(cfg["layer_types"])[1:]}


#: variant -> (what it changes of the program's configuration,
#: (module, attribute, wrapper) of what it patches in the program)
VARIANTS = {
    "no_state_carry": (None, (_SSD, "_carried_states", _dead_carry)),
    "conv_shifted": (None, (_SSD, "causal_conv_silu", _late_conv)),
    "no_d_skip": (None, (_SSD, "ssd_scan", _wrap_scan(_no_d))),
    "norm_before_gate": (None, (_SSD, "gated_rms_norm", _gate_after)),
    "dt_no_softplus": (None, (_SSD, "ssd_scan", _wrap_scan(_raw_dt))),
    "rope_on_attention": (
        lambda cfg: {"position_embedding_type": "rope"}, None),
    "sm_scale_sqrt": (lambda cfg: {"attention_multiplier": (
        int(cfg["dim"]) // int(cfg["n_heads"])) ** -0.5}, None),
    "no_residual_multiplier": (
        lambda cfg: {"residual_multiplier": 1.0}, None),
    "no_logits_scaling": (lambda cfg: {"logits_scaling": 1.0}, None),
    "untied_head": (lambda cfg: {"tie_word_embeddings": False}, None),
    "pattern_shifted": (_shifted, None),
}


def _moved_layers(name, cfg):
    """The two layers whose weights change places with their kinds
    under ``pattern_shifted``; None for every other variant."""
    if name != "pattern_shifted":
        return None
    at = list(cfg["layer_types"])[:int(cfg["n_layers"])].index("attention")
    return at - 1, at


def _swap(tree, pair):
    if pair is None:
        return tree
    i, j = pair
    layers = list(tree["layers"])
    layers[i], layers[j] = layers[j], layers[i]
    return dict(tree, layers=layers)


def _program_step(config, cfg, patch, p0, batch):
    """(loss, gradients, logits of the first sequence, scan counters)
    of one SGD step of the program built from ``cfg``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from theanompi_tpu.parallel import make_mesh

    cls = getattr(importlib.import_module(config["model"]["modelfile"]),
                  config["model"]["modelclass"])
    with _patched(patch):
        model = cls(cfg)
        model.build_model(n_replicas=1)
        model.compile_iter_fns(
            mesh=make_mesh(data=1, devices=jax.devices()[:1]))
        model.params = jax.device_put(p0, model._shardings(model._specs))
        spec = model._batch_sharding.spec
        forward = jax.jit(jax.shard_map(
            lambda p, ids: model._forward(p, ids), mesh=model.mesh,
            in_specs=(model._specs, spec), out_specs=jax.P(*spec, "model"),
        ))
        logits = np.asarray(forward(model.params, batch[0])[0], np.float32)
        p1, _, _, loss, _, ssm = model._train_step(
            model.params, model.opt_state, model.ef_state,
            *model.put_batch(batch), jnp.float32(SGD_RATE))
        loss = float(loss)
    grads = jax.tree.map(lambda a, b: (a - np.asarray(b)) / SGD_RATE,
                         p0, jax.device_get(p1))
    ssm = np.asarray(ssm, np.float64)
    model.params = p1 = None
    jax.clear_caches()
    return loss, grads, logits, ssm


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
        """(loss, gradients, first sequence's logits) of the reference
        as the module stands, a sequence at a time."""
        def seq_loss(p, ids, tgt):
            with jax.default_matmul_precision("highest"):
                return ref._sequence(
                    p, ids, tgt, dict(kw, block=jax.checkpoint)) / x.size

        one = jax.jit(jax.value_and_grad(seq_loss))
        loss, grads = 0.0, None
        for ids, tgt in zip(x, y):
            l, g = one(p0, ids, tgt)
            g = jax.tree.map(np.asarray, g)
            grads = g if grads is None else jax.tree.map(np.add, grads, g)
            loss += float(l)
        logits = np.asarray(
            jax.jit(lambda p, ids: ref.logits(p, ids, **kw))(p0, x[0]))
        del one
        jax.clear_caches()
        return loss, grads, logits, None

    ref_loss, ref_grads, ref_logits, _ = reference_step()
    ref_flat = _flat(ref_grads)
    top = float(np.max(np.abs(ref_logits)))

    def program_step(name):
        if name == CONTROL:
            with lower_precision(ref):
                return reference_step()
        if name is None:
            return _program_step(config, cfg, None, p0, batch)
        change, patch = VARIANTS[name]
        over = change(cfg) if change else {}
        pair = _moved_layers(name, cfg)
        start = _swap(p0, pair)
        if name == "untied_head":
            start = dict(start, lm_head=np.ascontiguousarray(p0["embed"].T))
        loss, grads, logits, ssm = _program_step(
            config, dict(cfg, **over), patch, start, batch)
        grads = _swap(grads, pair)
        grads.pop("lm_head", None)
        return loss, grads, logits, ssm

    def held_to_reference(name):
        loss, grads, logits, ssm = program_step(name)
        flat = _flat(grads)
        grad_rel = {k: _rel0(flat[k], ref_flat[k]) for k in ref_flat}
        worst = max(grad_rel, key=lambda k: (
            grad_rel[k] if np.isfinite(grad_rel[k]) else np.inf))
        got = {
            "loss": loss,
            "loss_rel": abs(loss - ref_loss) / abs(ref_loss),
            "grad_rel_worst": grad_rel[worst],
            "grad_rel_worst_leaf": worst,
            "grad_rel_embed": grad_rel["embed"],
            "logits_rel": float(np.max(np.abs(logits - ref_logits)) / top),
        }
        if ssm is not None:
            got["ssm_log_decay_min"] = ssm[:, 0].tolist()
            got["ssm_state_rms"] = ssm[:, 1].tolist()
        # (a NaN is no pass: every comparison with it is False)
        got["ok"] = bool(
            got["loss_rel"] <= LOSS_RTOL
            and got["grad_rel_worst"] <= GRAD_RTOL
            and got["logits_rel"] <= LOGITS_RTOL
        )
        print(json.dumps({"variant": name, **got}), flush=True)
        return dict(got, grad_rel=grad_rel)

    out = {
        "cell": cell_name, "seed": seed,
        "device": {"platform": jax.devices()[0].platform,
                   "kind": jax.devices()[0].device_kind},
        "tokens": int(x.size), "reference_loss": ref_loss,
        "reference_logits_max": top,
        "limits": {"loss_rel": LOSS_RTOL, "grad_rel": GRAD_RTOL,
                   "logits_rel": LOGITS_RTOL},
        "right": held_to_reference(None),
        "variants": {name: held_to_reference(name)
                     for name in [*variants, *([CONTROL] if control else [])]},
    }
    out["ok"] = out["right"]["ok"]
    out["failed"] = {n: not v["ok"] for n, v in out["variants"].items()}
    return out


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cell", default="granite4h_micro_train_t8192")
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
    print(json.dumps(out), flush=True)
    return 0 if out["ok"] and all(out["failed"].values()) else 1


if __name__ == "__main__":
    sys.exit(main())
