#!/usr/bin/env python3
"""Does the system still start on the chip?  One process, the normal
entry points, full widths, a few steps and a few requests — and a
check of what comes out.

``python chip_smoke.py`` needs ONE TPU chip and runs three phases:

1. ``train_resnet50`` — ``BSP().init(..., launch="inprocess")`` on
   ``models.resnet50.ResNet50`` (the paper's headline model) at its
   published width, batch 128, bf16, 224², synthetic data from a seed:
   one ``steps_per_call`` scan chunk, two single steps, one validation
   batch.
2. ``train_llama`` — the same rule and worker on the Llama proxy
   (8 layers x 1024, 16/8 heads of
   64, ffn 2816, vocab 32000, T 2048, batch 4, remat, ``ici16``); the
   compiled step must hold the flash kernels (``tpu_custom_call``),
   and the epoch ends in a checkpoint.
3. ``serve`` — ``decoder_from_checkpoint(..., paged=True)`` on that
   checkpoint, an ``Engine``, 8 greedy requests (prompts of 64..512
   tokens, 32 new tokens each), once over the ``gather`` attention and
   once over the ``pallas`` kernel compiled by Mosaic.  Every
   generated token of both runs is held against the training forward
   ``Llama._forward`` on the same tokens: it must be the argmax there,
   up to a tolerance on the logits stated from the compute dtype
   (bf16 decides near-ties differently on different code paths, and a
   wrong mask or position is off by the whole logit range).

``python chip_smoke.py --chips 4`` needs a four-chip host and runs
only what exists across chips, each against the same seed and global
batch on one chip of the same host: ResNet-50 at dp=4, and the Llama
proxy at dp=2 x tp=2.

Every phase prints one JSON line — wall, compile and run seconds (the
compile seconds are JAX's own, from ``jax.monitoring``), persistent-
cache hits and misses, steps, losses, tokens, which attention path the
HLO shows.  A line before them names the device, the compile-cache
directory and whether the native loader was built from ``loader.cc``
or its Python fallback is in use.  The LAST line is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

and the exit status is 0 only then: any other platform than ``tpu``,
a phase that raises, or a comparison that fails gives ``"ok": false``
and status 1.  Nothing here steers JAX: no ``JAX_PLATFORMS``, no
``TM_TPU_PLATFORM``, no default device.  The phases are functions of
their sizes so that ``tests/test_chip_smoke.py`` can rehearse the
control flow at tiny sizes on the CPU; the script itself has no size
option.
"""

from __future__ import annotations

import argparse
import gc
import json
import math
import tempfile
import time
import traceback

RESNET50 = dict(
    modelfile="theanompi_tpu.models.resnet50", modelclass="ResNet50",
    config=dict(batch_size=128, crop=224, exch_strategy="ici16"),
    n_batches=6, steps_per_call=4,
)
#: the Llama proxy, widths untouched.
#: The learning rate is the smoke's own: at the model's default (3e-3,
#: no warm-up) six steps collapse it onto ONE token whatever the
#: prompt (first chip run, PR 22), and a model that says one thing
#: passes any check of what it says.
LLAMA = dict(
    modelfile="theanompi_tpu.models.llama", modelclass="Llama",
    config=dict(
        dim=1024, n_layers=8, n_heads=16, n_kv_heads=8, ffn_dim=2816,
        vocab=32000, seq_len=2048, batch_size=4, remat=True,
        exch_strategy="ici16", n_val=0, lr=1e-4,
    ),
    n_batches=6, steps_per_call=4,
)
SERVE = dict(
    prompt_lens=(64, 128, 192, 256, 320, 384, 448, 512),
    max_tokens=32, max_slots=8, block_size=16, seed=0,
)
#: relative agreement of two first-step losses computed in bf16 on
#: different meshes (different reduction orders, same samples)
LOSS_RTOL = 1e-2


class CheckFailed(RuntimeError):
    """A phase ran but what came out is wrong."""


def _require(cond: bool, message: str) -> None:
    if not cond:
        raise CheckFailed(message)


def __getattr__(name: str):
    """``chip_smoke.CompileMeter`` is ``theanompi_tpu.obs``'s now; the
    name stays here, imported on use as everything of the package is
    (the script alone must still print its last line)."""
    if name == "CompileMeter":
        from theanompi_tpu.obs import CompileMeter

        return CompileMeter
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def run_phase(name: str, meter: CompileMeter, fn, *args, **kw) -> dict:
    """Run one phase, print its line, and leave the chip as it was
    found: the phase's model, dataset cache and executables are
    dropped before the next one needs the HBM."""
    import jax

    t0, before = time.perf_counter(), meter.read()
    line = {"phase": name, "ok": True}
    try:
        line.update(fn(*args, **kw))
    except Exception as e:
        traceback.print_exc()
        line.update(ok=False, error=f"{type(e).__name__}: {e}"[:2000])
    wall = time.perf_counter() - t0
    new = meter.since(before)
    line.update(
        wall_s=round(wall, 2), compile_s=round(new["compile_s"], 2),
        run_s=round(wall - new["compile_s"], 2),
        cache_hits=new["cache_hits"], cache_misses=new["cache_misses"],
    )
    gc.collect()
    jax.clear_caches()
    print(json.dumps(line), flush=True)
    return line


# -- facts about a trained model ---------------------------------------------


def _param_placement(model) -> dict:
    """Where the parameters live: platforms, number of distinct
    devices holding a shard, and bytes per device."""
    import jax

    per_device: dict = {}
    platforms = set()
    for leaf in jax.tree.leaves(model.params):
        for shard in leaf.addressable_shards:
            platforms.add(shard.device.platform)
            per_device[shard.device.id] = (
                per_device.get(shard.device.id, 0) + shard.data.nbytes
            )
    return {
        "param_platforms": sorted(platforms),
        "param_devices": len(per_device),
        "param_bytes_per_device": sorted(set(per_device.values())),
    }


def _tp_shard_fraction(model) -> float:
    """Bytes one device holds of the leaves sharded over the model
    axis, over their full bytes (1/tp when the sharding is real)."""
    import jax
    from jax.sharding import PartitionSpec

    from theanompi_tpu.parallel import MODEL_AXIS

    held = full = 0
    specs = jax.tree.leaves(
        model._specs, is_leaf=lambda s: isinstance(s, PartitionSpec)
    )
    for leaf, spec in zip(jax.tree.leaves(model.params), specs):
        axes = {
            a for part in spec if part is not None
            for a in (part if isinstance(part, tuple) else (part,))
        }
        if MODEL_AXIS in axes:
            held += leaf.addressable_shards[0].data.nbytes
            full += leaf.nbytes
    return held / full


def train(spec: dict, *, devices, checkpoint_dir=None,
          **config_over) -> tuple[dict, list]:
    """A few training steps of ``spec`` through the BSP rule, in this
    process.  ``devices`` is what ``Rule.init`` takes (indices; the
    worker builds the mesh).  Returns the phase's facts and the
    per-step losses."""
    from theanompi_tpu import BSP
    from theanompi_tpu.ops import attention

    cfg = dict(spec["config"], device_data_cache=True,
               steps_per_call=spec["steps_per_call"], **config_over)
    tp = int(cfg.get("tp", 1))
    cfg.setdefault(
        "n_train",
        spec["n_batches"] * cfg["batch_size"] * (len(devices) // tp),
    )
    # the classifiers' data object reads 0 as "default": one val batch
    cfg.setdefault("n_val", cfg["batch_size"] * (len(devices) // tp))
    dense_before = attention.dense_choices()

    rule = BSP()
    rule.init(
        devices=list(devices), modelfile=spec["modelfile"],
        modelclass=spec["modelclass"], launch="inprocess",
        config=cfg, n_epochs=1, checkpoint_dir=checkpoint_dir,
        verbose=False,
    )
    res = rule.wait()
    model, losses = res["model"], [
        float(x) for x in res["recorder"].train_losses
    ]
    _require(len(losses) == res["iterations"] > 0,
             f"no steps recorded: {res['iterations']}")
    _require(all(math.isfinite(x) for x in losses),
             f"non-finite loss: {losses}")
    hlo = model.train_step_hlo_text()
    facts = {
        "model": spec["modelclass"],
        "mesh": {k: v for k, v in model.mesh.shape.items() if v > 1},
        "global_batch": int(model.data.global_batch),
        "dtype": str(model.compute_dtype),
        "steps": len(losses),
        "scan_chunk": spec["steps_per_call"],
        "first_loss": losses[0], "last_loss": losses[-1],
        "hlo_tpu_custom_call": "tpu_custom_call" in hlo,
        "hlo_all_reduce": "all-reduce" in hlo,
        "dense_attention_choices": (
            attention.dense_choices() - dense_before
        ),
        **_param_placement(model),
    }
    if res["final_val"]:
        facts["val_loss"] = float(res["final_val"]["loss"])
        _require(math.isfinite(facts["val_loss"]), "non-finite val loss")
    if tp > 1:
        facts["tp_shard_fraction"] = round(_tp_shard_fraction(model), 4)
    if checkpoint_dir is not None:
        facts["checkpoint_dir"] = checkpoint_dir
    return facts, losses


def train_phase(spec: dict, devices, checkpoint_dir=None) -> dict:
    return train(spec, devices=devices, checkpoint_dir=checkpoint_dir)[0]


# -- serving -----------------------------------------------------------------


def _reference_logits_fn(model, t_ref: int):
    """``Llama._forward`` on the model's own mesh: ids [1, t_ref] ->
    logits [t_ref, vocab] in float32."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from theanompi_tpu.parallel import MODEL_AXIS

    ids_spec = model._batch_sharding.spec
    fn = jax.jit(jax.shard_map(
        lambda params, ids: model._forward(params, ids),
        mesh=model.mesh,
        in_specs=(model._specs, ids_spec),
        out_specs=P(*ids_spec, MODEL_AXIS),
    ))

    def logits(ids):
        padded = jnp.zeros((1, t_ref), jnp.int32).at[0, :len(ids)].set(
            jnp.asarray(ids, jnp.int32)
        )
        return fn(model.params, padded)[0].astype(jnp.float32)

    return logits


def _hold_to_reference(logits_fn, prompt, tokens, rtol: float) -> dict:
    """Teacher-forced check of one served stream: with the prompt and
    the stream's own earlier tokens as input, each token must be the
    reference's argmax at its position, or within ``rtol * |max|`` of
    it in reference logit (a near-tie that reduced-precision paths may
    break either way)."""
    import numpy as np

    n, lp = len(tokens), len(prompt)
    rows = np.asarray(
        logits_fn(list(prompt) + list(tokens[:-1]))[lp - 1:lp - 1 + n]
    )
    top = rows.max(axis=-1)
    gaps = top - rows[np.arange(n), np.asarray(tokens)]
    return {
        "first_is_argmax": bool(gaps[0] == 0.0),
        "n_argmax": int((gaps == 0.0).sum()),
        "n_within_tol": int((gaps <= rtol * np.abs(top)).sum()),
        "worst_gap_over_max": float((gaps / np.abs(top)).max()),
    }


def serve(spec: dict, model_config: dict, checkpoint_dir: str, device,
          *, pallas_interpret: bool = False) -> dict:
    """Serve ``spec``'s requests from the checkpoint over both
    attention implementations and hold both to the reference."""
    import jax.numpy as jnp
    import numpy as np

    from theanompi_tpu.serving import Engine, decoder_from_checkpoint

    lens, max_tokens = spec["prompt_lens"], spec["max_tokens"]
    rng = np.random.default_rng(spec["seed"])
    prompts = [
        [int(t) for t in rng.integers(1, model_config["vocab"], n)]
        for n in lens
    ]
    decoder_kw = dict(
        max_slots=spec["max_slots"], block_size=spec["block_size"],
    )
    cfg = dict(model_config, tp=1, device_data_cache=False,
               n_train=model_config["batch_size"], n_val=0)

    def run(decoder):
        engine = Engine(decoder, default_deadline_s=1200.0)
        futures = [
            engine.submit(p, max_tokens=max_tokens, seed=i)
            for i, p in enumerate(prompts)
        ]
        engine.run_until_idle()
        results = [f.result(timeout=0) for f in futures]
        bad = [
            (r.status, r.finish_reason, len(r.tokens)) for r in results
            if (r.status, r.finish_reason, len(r.tokens))
            != ("ok", "max_tokens", max_tokens)
        ]
        _require(not bad, f"requests did not complete: {bad}")
        return [list(r.tokens) for r in results], (
            "tpu_custom_call" in decoder.decode_hlo_text()
        )

    decoder = decoder_from_checkpoint(
        cfg, checkpoint_dir, devices=[device], paged=True,
        paged_attend_impl="gather", **decoder_kw,
    )
    model = decoder.model
    streams, kernel_in_hlo = {}, {}
    streams["gather"], kernel_in_hlo["gather"] = run(decoder)
    del decoder
    gc.collect()   # the gather decoder's pools, before the next ones
    decoder = model.make_decoder(
        paged=True, paged_attend_impl="pallas",
        pallas_interpret=pallas_interpret, **decoder_kw,
    )
    streams["pallas"], kernel_in_hlo["pallas"] = run(decoder)
    del decoder
    gc.collect()

    # one padded length for every request: one reference compile, and
    # a length the flash kernel tiles (a multiple of 16)
    t_ref = -(-(max(lens) + max_tokens) // 64) * 64
    logits_fn = _reference_logits_fn(model, t_ref)
    rtol = 4 * float(jnp.finfo(model.compute_dtype).eps)
    n_tokens = len(prompts) * max_tokens
    facts = {
        "requests": len(prompts), "prompt_lens": list(lens),
        "new_tokens_each": max_tokens, "tokens": n_tokens,
        "dtype": str(model.compute_dtype), "logit_rtol": rtol,
        "pallas_kernel": (
            "interpreted" if pallas_interpret else "mosaic"
        ),
        "hlo_tpu_custom_call": kernel_in_hlo,
        "streams_equal": sum(
            g == p for g, p in zip(streams["gather"], streams["pallas"])
        ),
        # a model that says one thing whatever it is asked would pass
        # the reference check trivially: show that this one does not
        "distinct_tokens": len(
            {t for toks in streams["gather"] for t in toks}
        ),
    }
    for impl, toks in streams.items():
        held = [
            _hold_to_reference(logits_fn, p, t, rtol)
            for p, t in zip(prompts, toks)
        ]
        facts[impl] = {
            "first_token_is_reference_argmax": sum(
                h["first_is_argmax"] for h in held
            ),
            "tokens_reference_argmax": sum(h["n_argmax"] for h in held),
            "tokens_within_tol": sum(h["n_within_tol"] for h in held),
            "worst_gap_over_max": max(
                h["worst_gap_over_max"] for h in held
            ),
        }
        _require(
            facts[impl]["tokens_within_tol"] == n_tokens,
            f"{impl}: served tokens off the reference argmax by more "
            f"than rtol={rtol}: {facts[impl]}",
        )
    _require(
        facts["distinct_tokens"] >= len(prompts),
        f"the model says {facts['distinct_tokens']} distinct tokens "
        f"over {len(prompts)} prompts: too few for the reference "
        f"check to mean anything",
    )
    # prefill is the same program in both decoders
    _require(
        all(g[0] == p[0]
            for g, p in zip(streams["gather"], streams["pallas"])),
        "first tokens differ between gather and pallas decoders",
    )
    return facts


# -- four chips --------------------------------------------------------------


def compare_data_parallel(spec: dict, n: int) -> dict:
    """``spec`` at dp=n over all chips, against the same seed and
    global batch on chip 0.  Each replica normalizes its own batch
    (BatchNorm statistics are per replica), and the global batch does
    not fit one chip's HBM in one step, so the one-chip side takes the
    same samples as n steps of the per-replica batch with the learning
    rate at 0: the mean of those losses is what the dp=n first step
    computes."""
    b = spec["config"]["batch_size"]
    n_train = spec["n_batches"] * b * n
    facts, losses = train(
        spec, devices=range(n), n_train=n_train, n_val=b * n,
    )
    one, one_losses = train(
        dict(spec, steps_per_call=n), devices=range(1),
        n_train=n_train, n_val=b, lr=0.0,
    )
    ref = sum(one_losses[:n]) / n
    facts["one_chip_first_loss"] = ref
    facts["one_chip_param_platforms"] = one["param_platforms"]
    facts["first_loss_rel_diff"] = abs(losses[0] - ref) / abs(ref)
    _require(facts["first_loss_rel_diff"] <= LOSS_RTOL,
             f"dp={n} first-step loss {losses[0]} vs one chip {ref}")
    _require(facts["hlo_all_reduce"], "no all-reduce in the dp step")
    _require(
        facts["param_devices"] == n
        and len(facts["param_bytes_per_device"]) == 1,
        f"parameters not replicated on {n} devices: {facts}",
    )
    return facts


def compare_tensor_parallel(spec: dict, dp: int, tp: int) -> dict:
    """``spec`` at dp x tp over all chips against its first-step loss
    at the same global batch on chip 0."""
    b = spec["config"]["batch_size"]
    n_train = spec["n_batches"] * b * dp
    facts, losses = train(
        spec, devices=range(dp * tp), tp=tp, n_train=n_train,
    )
    _, one_losses = train(
        spec, devices=range(1), batch_size=b * dp, n_train=n_train,
    )
    facts["one_chip_first_loss"] = one_losses[0]
    facts["first_loss_rel_diff"] = (
        abs(losses[0] - one_losses[0]) / abs(one_losses[0])
    )
    _require(facts["first_loss_rel_diff"] <= LOSS_RTOL,
             f"dp={dp} x tp={tp} first-step loss {losses[0]} vs one "
             f"chip {one_losses[0]}")
    _require(facts["hlo_all_reduce"], "no all-reduce in the step")
    _require(facts["param_devices"] == dp * tp,
             f"parameters on {facts['param_devices']} devices")
    _require(abs(facts["tp_shard_fraction"] - 1 / tp) <= 0.05,
             f"tp-sharded leaves hold {facts['tp_shard_fraction']} of "
             f"their bytes per device, want {1 / tp}")
    return facts


# -- what the chip contract asks of the facts --------------------------------


def off_chip(line: dict) -> list[str]:
    """Reasons a phase's facts are not a chip run's: things a CPU
    rehearsal of the same phase legitimately differs in."""
    why = []
    if line.get("param_platforms", ["tpu"]) != ["tpu"]:
        why.append(f"parameters on {line['param_platforms']}")
    if line.get("model") == "Llama" and not (
        line["hlo_tpu_custom_call"]
        and line["dense_attention_choices"] == 0
    ):
        why.append("flash kernels are not in the compiled step")
    if line.get("phase") == "serve":
        if line["pallas_kernel"] != "mosaic":
            why.append("paged-attention kernel was interpreted")
        if line["hlo_tpu_custom_call"] != {"gather": False,
                                           "pallas": True}:
            why.append(
                f"decode HLO kernels: {line['hlo_tpu_custom_call']}"
            )
    return why


def run_plan(chips: int, device, meter: CompileMeter) -> bool:
    """The phases for ``chips``, in order, each building on the one
    before; False at the first that fails or is not a chip run."""
    with tempfile.TemporaryDirectory() as ckpt:
        if chips == 1:
            plan = [
                ("train_resnet50", train_phase, RESNET50, [0]),
                ("train_llama", train_phase, LLAMA, [0], ckpt),
                ("serve", serve, SERVE, LLAMA["config"], ckpt, device),
            ]
        else:
            plan = [
                ("resnet50_dp4", compare_data_parallel, RESNET50, 4),
                ("llama_dp2_tp2", compare_tensor_parallel, LLAMA, 2, 2),
            ]
        for name, fn, *args in plan:
            line = run_phase(name, meter, fn, *args)
            if not line["ok"]:
                return False
            why = off_chip(line)
            if why:
                print(json.dumps({"phase": name, "off_chip": why}),
                      flush=True)
                return False
    return True


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--chips", type=int, choices=(1, 4), default=1,
        help="4: only the two multi-chip comparisons, on a four-chip "
        "host (default 1: train, train, serve on one chip)",
    )
    ns = ap.parse_args(argv)
    t_start = time.perf_counter()

    device = None
    ok = False
    try:
        import jax

        from theanompi_tpu import native
        from theanompi_tpu.obs import process_meter
        from theanompi_tpu.utils import enable_compile_cache

        devices = jax.devices()
        device = {
            "platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
        }
        cache_dir = enable_compile_cache()
        meter = process_meter()
        t0 = time.perf_counter()
        native_lib = native.load_native()
        print(json.dumps({
            "phase": "setup", **device, "jax": jax.__version__,
            "compile_cache_dir": cache_dir,
            "native_loader": (
                "built from loader.cc" if native_lib is not None
                else "python fallback"
            ),
            "native_loader_s": round(time.perf_counter() - t0, 2),
        }), flush=True)
        if device["platform"] != "tpu":
            raise CheckFailed(
                f"JAX's devices are {device['platform']!r}, not 'tpu'"
            )
        if device["count"] < ns.chips:
            raise CheckFailed(
                f"--chips {ns.chips} on a host with {device['count']}"
            )

        ok = run_plan(ns.chips, devices[0], meter)
    except Exception as e:
        traceback.print_exc()
        print(json.dumps({"phase": "failed",
                          "error": f"{type(e).__name__}: {e}"[:2000]}),
              flush=True)
    print(json.dumps({"phase": "total",
                      "wall_s": round(time.perf_counter() - t_start, 2)}),
          flush=True)
    print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
