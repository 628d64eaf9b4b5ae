"""The builder's check of a MoE decoder cell against its plain
reference at the PUBLISHED widths, outside any timed window (guide
``model-configs`` section 3, point 3):

    python3 -m benchmark.tools.olmoe_check [--cell olmoe_train_t4096] [--seed n]
        [--variant renormalised|dropping|no_qk_norm]

One batch of the cell (4 x 4096 tokens) goes through the model's own
train step — built from the cell's configuration with plain SGD at a
power-of-two rate in place of Adam, so that ``(before - after) / rate``
IS the step's gradient, exchange and all — and through
``reference/<module>.py`` in float32 at ``highest`` precision, on the
same weights.  Held, each against a written limit:

- the step's loss (cross-entropy plus both router terms);
- every leaf's gradient, by the norm of the difference over the
  reference's norm;
- the share of the batch's (token, pick) choices (131 072) on which
  program and reference agree as sets (bf16 router inputs flip
  near-ties at the 8th pick);
- the first sequence's logits, on the positions where all picks
  agree, by the largest difference over the largest logit.

``--variant`` builds the PROGRAM wrong on purpose (Mixtral's
renormalised gates, capacity buffers that drop, no QK-norm) while the
reference stays right: each must fail, which is what shows the limits
are tight enough.  The last line is a JSON object with every number
and ``ok``; the exit code is 0 when ``ok`` is what was expected.

The limits.  bf16 compute against a float32 reference is noisy in
this program: at these widths on the chip a right program differs
from the reference by 0.046-0.060 of a leaf's gradient norm, agrees
on 0.979-0.981 of the picks and, where all picks of a position agree,
on the logits to 0.020-0.021 of the largest (my chip runs e2 and e3,
two seeds, PR 26; the same
levels show on the CPU at small widths with ``compute_dtype``
bfloat16, and 1e-6 with float32, so it is the precision and not the
architecture).  Each limit lies between that and what the three wrong
programs read in the same run:

- ``LOSS_RTOL`` 2e-4, as ``drivers/train.py``'s (right 1.4e-5; the
  wrong ones 1.2e-5 to 3.6e-5: at initialisation the loss is ln(V)
  whatever the architecture, which is why the loss alone proves
  little).
- ``GRAD_RTOL`` 0.08 of the worst leaf's norm (right 0.060;
  renormalised 0.162, dropping 0.292, no QK-norm 0.158).
- ``PICK_AGREEMENT`` 0.95 (right 0.979 of the batch's 131 072 picks;
  no QK-norm 0.895; the other two leave the picks alone).
- ``LOGIT_TOL`` 0.04 of the largest logit where the picks agree
  (right 0.020; renormalised 0.326, dropping 0.615, no QK-norm 0.114).
"""

from __future__ import annotations

import argparse
import importlib
import json
import sys

LOSS_RTOL = 2e-4
GRAD_RTOL = 0.08
PICK_AGREEMENT = 0.95
LOGIT_TOL = 0.04
SGD_RATE = 2.0 ** 14

VARIANTS = {
    "renormalised": {"moe_renormalize": True},
    "dropping": {"capacity_factor": 1.0},
    "no_qk_norm": {"qk_norm": False},
}


def _rel(got, want) -> float:
    import numpy as np

    want = np.asarray(want, np.float64)
    return float(np.linalg.norm(np.asarray(got, np.float64) - want)
                 / np.linalg.norm(want))


def _flat(tree) -> dict:
    """``{"layers.0.wq": leaf, ...}``"""
    import jax

    return {
        ".".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path): v
        for path, v in jax.tree_util.tree_leaves_with_path(tree)
    }


def check(cell_name: str, seed: int, variant: str | None,
          rehearsal: bool = False) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ..drivers.train import program_config
    from ..run import load_cell

    import theanompi_tpu.models.llama as llama_mod
    from theanompi_tpu.parallel import make_mesh
    from theanompi_tpu.parallel.moe import router_topk

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

    # the program, with an ear on the router's picks
    picked: list = []
    real_ffn = llama_mod.moe_ffn

    def listening(x, w_router, *a, **k):
        _, eidx, _, _ = router_topk(x.reshape(-1, x.shape[-1]), w_router,
                                    k["top_k"], k["renormalize"])
        jax.debug.callback(lambda e: picked.append(np.asarray(e)), eidx)
        return real_ffn(x, w_router, *a, **k)

    model = getattr(importlib.import_module(config["model"]["modelfile"]),
                    config["model"]["modelclass"])(cfg)
    model.build_model(n_replicas=1)
    model.compile_iter_fns(mesh=make_mesh(data=1, devices=jax.devices()[:1]))
    p0 = jax.tree.map(np.asarray, jax.device_get(model.params))
    model.data.shuffle(0)
    x, y = (np.asarray(a) for a in model.data.train_batch(0))

    llama_mod.moe_ffn = listening
    try:
        batch = model._batch_sharding.spec
        forward = jax.jit(jax.shard_map(
            lambda p, ids: model._forward(p, ids), mesh=model.mesh,
            in_specs=(model._specs, batch),
            out_specs=jax.P(*batch, "model"),
        ))
        logits = np.asarray(forward(model.params, x)[0], np.float32)
        jax.effects_barrier()
    finally:
        llama_mod.moe_ffn = real_ffn
    # [L, B*T, k] as the layers ran -> [B, L, T, k]
    program_picks = np.stack(picked).reshape(
        len(picked), *x.shape, -1).swapaxes(0, 1)

    p1, _, _, loss, _, routing = model._train_step(
        model.params, model.opt_state, model.ef_state,
        *model.put_batch((x, y)), jnp.float32(SGD_RATE))
    loss = float(loss)
    routing = np.asarray(routing)
    grads = jax.tree.map(lambda a, b: (a - np.asarray(b)) / SGD_RATE,
                         p0, jax.device_get(p1))
    model.params = p1 = None
    jax.clear_caches()

    # the reference, on the weights the reference's architecture has
    # (a variant without QK-norm has no such leaves: they are ones)
    ref_params = p0
    if variant == "no_qk_norm":
        ref_params = dict(p0, layers=[
            dict(lp, q_norm=np.ones(lp["wq"].shape[1], np.float32),
                 k_norm=np.ones(lp["wk"].shape[1], np.float32))
            for lp in p0["layers"]
        ])
    ref_loss, ref_grads = jax.jit(jax.value_and_grad(
        lambda p: ref.loss(p, x, y, **kw)))(ref_params)
    ref_loss = float(ref_loss)
    ref_grads = jax.device_get(ref_grads)
    ref_forward = jax.jit(
        lambda p, ids: ref.logits_at(p, ids, jnp.arange(ids.shape[0]), **kw))
    ref_logits, first_picks = ref_forward(ref_params, x[0])
    ref_logits = np.asarray(ref_logits)
    ref_picks = np.stack([np.asarray(first_picks)] + [
        np.asarray(ref_forward(ref_params, ids)[1]) for ids in x[1:]])

    same = np.sort(program_picks, -1) == np.sort(ref_picks, -1)
    rows = same[0].all(axis=(0, 2))     # first sequence: positions, all layers
    flat, ref_flat = _flat(grads), _flat(ref_grads)
    grad_rel = {k: _rel(flat[k], ref_flat[k]) for k in flat}
    top = float(np.max(np.abs(ref_logits)))
    out = {
        "cell": cell_name, "seed": seed, "variant": variant,
        "device": {"platform": jax.devices()[0].platform,
                   "kind": jax.devices()[0].device_kind},
        "tokens": int(x.size), "picks_compared": int(same.size),
        "loss": loss, "reference_loss": ref_loss,
        "loss_rel": abs(loss - ref_loss) / abs(ref_loss),
        "grad_rel": grad_rel,
        "grad_rel_worst": max(grad_rel.values()),
        "pick_agreement": float(same.mean()),
        "positions_all_picks_agree": float(rows.mean()),
        "logit_diff_where_picks_agree": float(
            np.max(np.abs(logits[rows] - ref_logits[rows])) / top
        ) if rows.any() else None,
        "logit_diff_all_positions": float(
            np.max(np.abs(logits - ref_logits)) / top),
        "dropped_picks_in_the_step": float(routing[:, -1].sum()),
        "limits": {"loss_rel": LOSS_RTOL, "grad_rel": GRAD_RTOL,
                   "pick_agreement": PICK_AGREEMENT, "logit": LOGIT_TOL},
    }
    out["ok"] = bool(
        out["loss_rel"] <= LOSS_RTOL
        and out["grad_rel_worst"] <= GRAD_RTOL
        and out["pick_agreement"] >= PICK_AGREEMENT
        and out["logit_diff_where_picks_agree"] is not None
        and out["logit_diff_where_picks_agree"] <= LOGIT_TOL
    )
    return out


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cell", default="olmoe_train_t4096")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--variant", choices=sorted(VARIANTS))
    args = ap.parse_args(argv)
    out = check(args.cell, args.seed, args.variant)
    print(json.dumps(out), flush=True)
    return 0 if out["ok"] == (args.variant is None) else 1


if __name__ == "__main__":
    sys.exit(main())
