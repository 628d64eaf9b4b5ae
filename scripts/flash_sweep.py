"""Time the three flash-attention kernels alone on the chip, one tile
choice after another, and hold them to the dense reference.

    chiprun -- python3 scripts/flash_sweep.py [--parent DIR] [--quick]

Each kernel is timed by the host clock over a chain of calls inside
one jitted loop (an output feeds the next call, so nothing is elided);
lines go to ``chiprun_out/flash_sweep.jsonl`` and to the output.
``--parent DIR`` also times the kernels of a checkout
(``DIR/theanompi_tpu/ops/attention.py``, same entry points) and
reports both against the float32 reference.  The shape function
``ops.attention._flash_tiles`` holds what this sweep chose (PERF.md,
PR 32, whose own parent had (block_q, block_k) entry points: its rows
of that table came from an adapter this file no longer carries);
nothing in the package reads this file.
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


def timers(mod, causal, sm):
    """{kernel: f(tiles, q, k, v, g, lse, delta) -> ms a call} for the
    kernels of ``mod`` (an ``ops.attention``)."""

    def fwd(tiles, q, k, v, g, lse, delta):
        step = lambda _, q: mod._flash_fwd_call(  # noqa: E731
            q, k, v, causal, sm, tiles, False)[0]
        return _time(jax.jit(lambda q: jax.lax.fori_loop(0, CHAIN, step, q)), q)

    def bwd(which):
        def run(tiles, q, k, v, g, lse, delta):
            # XLA drops the kernel whose result is unused
            both = lambda q, k, v: mod._flash_bwd_call(  # noqa: E731
                q, k, v, g, lse, delta, causal, sm,
                tiles if which == "dkv" else _UNTIMED,
                tiles if which == "dq" else _UNTIMED, False)
            if which == "dkv":
                step = lambda _, kv: both(q, *kv)[1:]  # noqa: E731
                chain = jax.jit(lambda k, v: jax.lax.fori_loop(0, CHAIN, step, (k, v)))
                return _time(chain, k, v)
            step = lambda _, q: both(q, k, v)[0]  # noqa: E731
            return _time(jax.jit(lambda q: jax.lax.fori_loop(0, CHAIN, step, q)), q)
        return run

    return dict(fwd=fwd, dkv=bwd("dkv"), dq=bwd("dq"))


_UNTIMED = A.FlashTiles(512, 512, 512)   # tiles of the backward kernel not being timed


def check(mod, shape, seed):
    """Max abs error of out / dq / dk / dv of ``mod``'s kernels
    against dense float32 attention on the same bf16 inputs."""
    ks = jax.random.split(jax.random.key(seed), 4)
    q, k, v, g = (jax.random.normal(kk, shape, jnp.float32).astype(jnp.bfloat16) for kk in ks)
    f32 = lambda x: x.astype(jnp.float32)  # noqa: E731
    ref, vjp = jax.vjp(lambda q, k, v: A.mha_reference(q, k, v, causal=True), f32(q), f32(k), f32(v))
    want = (ref,) + vjp(f32(g))
    out, vjp = jax.vjp(lambda q, k, v: mod.flash_attention_tpu(q, k, v, causal=True), q, k, v)
    got = (out,) + vjp(g)
    return {n: float(jnp.max(jnp.abs(f32(a) - b))) for n, a, b in zip(("out", "dq", "dk", "dv"), got, want)}, got


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


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--parent", default=None)
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--kernels", default="fwd,dkv,dq")
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

    for seed in (1, 2):
        new, got_new = check(A, (1, 4, 1024, 128), seed)
        emit(check="change", seed=seed, **new)
        if parent:
            old, got_old = check(parent, (1, 4, 1024, 128), seed)
            emit(check="parent", seed=seed, **old)
            emit(check="change_vs_parent", seed=seed, **{
                n: float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))))
                for n, a, b in zip(("out", "dq", "dk", "dv"), got_new, got_old)})

    shape = (2, 32, 4096, 128)
    kq = jax.random.split(jax.random.key(0), 4)
    q, k, v, g = (jax.random.normal(kk, shape, jnp.float32).astype(jnp.bfloat16) for kk in kq)
    lse = jnp.full(shape[:3], 8.0, jnp.float32)
    delta = jnp.zeros(shape[:3], jnp.float32)
    sm = shape[-1] ** -0.5
    operands = (q, k, v, g, lse, delta)

    tiles_now = {n: tuple(t) for n, t in A._flash_tiles(4096, 4096, 128, jnp.bfloat16)._asdict().items()}
    if parent:
        for name, fn in timers(parent, True, sm).items():
            emit(kernel=name, tree="parent", tiles=list(tiles_now[name]),
                 ms=fn(A.FlashTiles(*tiles_now[name]), *operands))

    if args.quick:
        grid = {n: [t] for n, t in tiles_now.items()}
    else:
        base = [(r, m, s) for r, m in itertools.product((256, 512, 1024), (1024, 4096))
                for s in (128, 256, 512) if not (s == 128 and m == 4096)]
        base += [(1024, 1024, 1024), (512, 2048, 256), (512, 2048, 512), (512, 512, 512), (256, 256, 256)]
        grid = dict(fwd=base, dkv=base, dq=base)
    mine = timers(A, True, sm)
    for name in args.kernels.split(","):
        for tiles in grid[name]:
            try:
                ms = mine[name](A.FlashTiles(*tiles), *operands)
                emit(kernel=name, tree="change", tiles=list(tiles), ms=ms)
            except Exception as e:  # a refused tile is a row of the table
                emit(kernel=name, tree="change", tiles=list(tiles), error=str(e)[:300])


if __name__ == "__main__":
    main()
