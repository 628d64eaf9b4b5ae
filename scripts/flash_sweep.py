"""Time the two flash-attention kernels alone on the chip, one tile
choice after another, and hold them to the dense reference.

    chiprun -- python3 scripts/flash_sweep.py [--parent DIR] [--grid 0]

Each kernel is timed by the host clock over a chain of calls inside
one jitted loop (an output feeds the next call, so nothing is elided);
lines go to ``chiprun_out/flash_sweep.jsonl`` and to the output.
``--parent DIR`` also times the backward of a checkout
(``DIR/theanompi_tpu/ops/attention.py``) at its own tiles and holds
this tree's gradients to its (the ``check`` lines): a checkout whose
backward is the one kernel through the same entry point, one from
before PR 54 (a dK/dV and a dQ kernel, ``_flash_bwd_call(..,
dkv_tiles, dq_tiles, ..)``) as the ``pair`` and each of the two alone.
The shape function ``ops.attention._flash_tiles`` holds what this
sweep chose (PERF.md, PR 32 and PR 54); nothing in the package reads
this file.
"""
import argparse
import importlib.util
import itertools
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from theanompi_tpu.ops import attention as A  # noqa: E402

CHAIN = 10


def _time(fn, *args):
    out = fn(*args)
    jax.block_until_ready(out)
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best / CHAIN * 1e3


def _chain(step, carried, fixed):
    """ms a call of ``step(*carried, *fixed) -> new carried``, chained.
    Every operand is an argument of the jitted loop: one closed over
    would be compiled in as a constant of hundreds of megabytes."""
    loop = jax.jit(lambda carried, fixed: jax.lax.fori_loop(
        0, CHAIN, lambda _, x: tuple(step(*x, *fixed)), carried))
    return _time(loop, carried, fixed)


def backward(mod, plan, causal, sm, window=None):
    """``f(q, k, v, g, lse, delta) -> (dq, dk, dv)`` of ``mod`` (an
    ``ops.attention``) under ``plan``; a checkout from before PR 54
    runs its pair, under its own entry point."""
    if "bwd" in mod.FlashPlan._fields:
        return lambda *a: mod._flash_bwd_call(*a, causal, sm, plan, False, window)
    return lambda *a: mod._flash_bwd_call(
        *a, causal, sm, plan.dkv, plan.dq, False, window)


def timers(mod, causal, sm, window=None):
    """{kernel: f(plan, q, k, v, g, lse, delta) -> ms a call}: ``fwd``,
    ``bwd`` (the whole backward: one kernel, or a pair) and, of a
    pair, ``dkv`` and ``dq`` alone (XLA drops the kernel whose result
    is unused)."""

    def run(plan):
        return backward(mod, plan, causal, sm, window)

    return dict(
        fwd=lambda plan, q, k, v, *rest: _chain(
            lambda q, k, v: mod._flash_fwd_call(
                q, k, v, causal, sm, plan.fwd, False, window)[:1], (q,), (k, v)),
        bwd=lambda plan, q, k, v, *rest: _chain(run(plan), (q, k, v), rest),
        dkv=lambda plan, q, k, v, *rest: _chain(
            lambda k, v, q, *r: run(plan)(q, k, v, *r)[1:], (k, v), (q, *rest)),
        dq=lambda plan, q, k, v, *rest: _chain(
            lambda q, *r: run(plan)(q, *r)[:1], (q,), (k, v, *rest)),
    )


def _draw(shape, seed):
    ks = jax.random.split(jax.random.key(seed), 4)
    return tuple(jax.random.normal(kk, shape, jnp.float32).astype(jnp.bfloat16) for kk in ks)


def check(mod, shape, seed, window=None):
    """Max abs error of out / dq / dk / dv of ``mod``'s kernels against
    dense float32 attention on the same bf16 inputs, and the arrays."""
    q, k, v, g = _draw(shape, seed)
    f32 = lambda x: x.astype(jnp.float32)  # noqa: E731
    ref, vjp = jax.vjp(
        lambda q, k, v: A.mha_reference(q, k, v, causal=True, window=window),
        f32(q), f32(k), f32(v))
    want = (ref,) + vjp(f32(g))
    out, vjp = jax.vjp(
        lambda q, k, v: mod.flash_attention_tpu(q, k, v, causal=True, window=window),
        q, k, v)
    got = (out,) + vjp(g)
    return _errors(got, want), got


def _errors(xs, ys):
    return {n: float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))))
            for n, a, b in zip(("out", "dq", "dk", "dv"), xs, ys)}


def mxu_probe():
    """What the matrix unit does with float32 operands of a Pallas
    product: the result against float64 products of the operands as
    they are, rounded to bf16 (nearest even) and truncated to bf16."""
    from jax.experimental import pallas as pl

    def kernel(a_ref, b_ref, o_ref):
        o_ref[...] = jax.lax.dot_general(
            a_ref[...], b_ref[...], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    ka, kb = jax.random.split(jax.random.key(7))
    a = jax.random.normal(ka, (256, 256), jnp.float32)
    b = jax.random.normal(kb, (256, 256), jnp.float32)
    got = np.asarray(pl.pallas_call(
        kernel, out_shape=jax.ShapeDtypeStruct((256, 256), jnp.float32))(a, b), np.float64)
    a64, b64 = np.asarray(a, np.float64), np.asarray(b, np.float64)
    rne = lambda x: np.asarray(jnp.asarray(x, jnp.float32).astype(jnp.bfloat16).astype(jnp.float32), np.float64)  # noqa: E731
    trunc = lambda x: (np.asarray(x, np.float32).view(np.uint32) & np.uint32(0xFFFF0000)).view(np.float32).astype(np.float64)  # noqa: E731
    err = lambda w: float(np.max(np.abs(got - w)))  # noqa: E731
    return dict(
        vs_f32_operands=err(a64 @ b64), vs_bf16_nearest=err(rne(a64) @ rne(b64)),
        vs_bf16_truncated=err(trunc(a64) @ trunc(b64)),
        vs_bf16_lhs_only=err(rne(a64) @ b64), scale=float(np.max(np.abs(a64 @ b64))))


# (batch-heads, T, head dim, window): the hybrid cell's call, the
# Mistral, OLMoE and Ouro cells', GLM's, Laguna's full layers, Mellum's
# and Laguna's window layers
SHAPES = [
    (32, 8192, 64, None), (64, 4096, 128, None), (40, 8192, 256, None),
    (48, 8192, 128, None), (64, 8192, 128, 1024), (72, 8192, 128, 512),
]
# held to dense attention (and to ``--parent``'s): every head dim the
# cells hand the kernels, and a band
CHECKS = [(2048, 64, None), (2048, 128, None), (2048, 256, None), (2048, 128, 512)]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--parent", default=None)
    ap.add_argument("--grid", default="0,1,2,3",
                    help="indices into SHAPES of the shapes also swept over a "
                         "grid of tiles ('' for none)")
    ap.add_argument("--out", default="chiprun_out/flash_sweep.jsonl")
    args = ap.parse_args()
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    sink = open(args.out, "a")

    def emit(**line):
        print(json.dumps(line), flush=True)
        sink.write(json.dumps(line) + "\n")
        sink.flush()

    emit(device=str(jax.devices()[0].device_kind), n=len(jax.devices()))
    emit(mxu_probe=mxu_probe())

    parent = None
    if args.parent:
        spec = importlib.util.spec_from_file_location(
            "parent_attention", os.path.join(args.parent, "theanompi_tpu/ops/attention.py"))
        parent = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(parent)

    for (t, d, window), seed in itertools.product(CHECKS, (1, 2)):
        line = dict(shape=[4, t, d], window=window, seed=seed)
        new, got_new = check(A, (1, 4, t, d), seed, window)
        emit(check="change", **line, **new)
        if parent:
            old, got_old = check(parent, (1, 4, t, d), seed, window)
            emit(check="parent", **line, **old)
            emit(check="change_vs_parent", **line, **_errors(got_new, got_old))

    grid = {int(i) for i in args.grid.split(",") if i}
    for index, (bh, t, d, window) in enumerate(SHAPES):
        shape = (1, bh, t, d)
        q, k, v, g = _draw(shape, 0)
        lse = jnp.full(shape[:3], 8.0, jnp.float32)
        delta = jnp.zeros(shape[:3], jnp.float32)
        operands = (q, k, v, g, lse, delta)
        plan = A._flash_tiles(t, t, d, jnp.bfloat16, window)
        mine = timers(A, True, d ** -0.5, window)

        def timed(kernel, plan, tiles, tree="change", of=mine):
            line = dict(kernel=kernel, tree=tree, shape=[bh, t, d], window=window,
                        tiles=list(tiles))
            try:
                emit(ms=of[kernel](plan, *operands), **line)
            except Exception as e:  # a refused tile is a row of the table
                emit(error=str(e)[:300], **line)

        timed("fwd", plan, plan.fwd)
        timed("bwd", plan, plan.bwd)
        if parent:
            theirs = parent._flash_tiles(t, t, d, jnp.bfloat16, window)
            of = timers(parent, True, d ** -0.5, window)
            if hasattr(theirs, "dkv"):
                for kernel in ("bwd", "dkv", "dq"):
                    timed(kernel, theirs, theirs.dq if kernel == "dq" else theirs.dkv,
                          "parent", of)
            else:
                timed("bwd", theirs, theirs.bwd, "parent", of)
        if index not in grid:
            continue
        majors = sorted({m for m in (1024, 2048, 4096, 8192) if m <= t})
        for rows, major, sub in itertools.product((256, 512, 1024), majors, (256, 512)):
            tiles = A.FlashTiles(rows, major, sub)
            timed("bwd", A.FlashPlan(fwd=plan.fwd, bwd=tiles), tiles)
        for major in majors:
            tiles = A.FlashTiles(512, major, 512)
            timed("fwd", A.FlashPlan(fwd=tiles, bwd=plan.bwd), tiles)


if __name__ == "__main__":
    main()
