"""Time the ways a sigmoid router can get its picked scores, alone on
the chip, and hold each to the plain form bit for bit.

    chiprun -- python3 scripts/router_sweep.py

The plain form is what ``parallel/moe.router_topk`` did up to PR 55:
``take_along_axis(scores, top_k(scores + bias)[1])``, an element
gather of ``k N`` single scores.  The candidates give the same
``(gates, eidx)`` without it; ``landed`` is ``router_topk`` itself
on an identity router, held to ``landed_plain`` (the plain form
behind the same product).  Each form is timed by
the host clock over a chain of calls inside one jitted loop (every
call's logits differ by the loop index, so nothing is hoisted, and
the results are summed into the carry, so nothing is elided), at the
two shapes the cells run: ``[16384, 512]`` top 22 (Nemotron) and
``[16384, 64]`` top 4 (GLM), float32; forward alone, and with the
gradient to the logits of a router whose gates carry one.  One JSON
line a form and shape, to the output and to
``chiprun_out/router_sweep.jsonl``.  Nothing in the package reads this
file; it imports nothing a cell runs but ``router_topk`` (PERF.md,
PR 56).
"""
import argparse
import functools
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from theanompi_tpu.parallel.moe import router_topk  # noqa: E402

CHAIN = 20
# (tokens, experts, picks): Nemotron's and GLM's router a layer call
SHAPES = [(16384, 512, 22), (16384, 64, 4)]


def plain(scores, chosen, k):
    _, eidx = lax.top_k(chosen, k)
    return jnp.take_along_axis(scores, eidx, axis=-1), eidx


def sort3(scores, chosen, k):
    """(i) the sort carries the scores: stable on the negated key is
    ``top_k``'s order."""
    iota = lax.broadcasted_iota(jnp.int32, chosen.shape, 1)
    _, s, i = lax.sort((-chosen, scores, iota), dimension=1, num_keys=1,
                       is_stable=True)
    return s[:, :k], i[:, :k]


def sort3_int(scores, chosen, k):
    """(i) on a whole-number key: a float's bits, the negative ones
    flipped, order as the floats do (``chosen`` is never ``-0``: a
    sigmoid is not, and ``x + (-x)`` rounds to ``+0``), so the sort's
    comparator is ONE integer compare where a float key's also
    canonicalises zeros and NaNs; ``~`` turns the order round."""
    bits = lax.bitcast_convert_type(chosen, jnp.int32)
    key = ~jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)
    iota = lax.broadcasted_iota(jnp.int32, chosen.shape, 1)
    _, s, i = lax.sort((key, scores, iota), dimension=1, num_keys=1,
                       is_stable=True)
    return s[:, :k], i[:, :k]


def select_sum(scores, chosen, k):
    """(ii) compare, select, sum: one term of each sum is not zero."""
    _, eidx = lax.top_k(chosen, k)
    hit = eidx[:, :, None] == jnp.arange(scores.shape[-1], dtype=eidx.dtype)
    return jnp.sum(jnp.where(hit, scores[:, None, :], 0.0), axis=-1), eidx


def two_sorts(scores, chosen, k):
    """(iii) the picks from ``top_k``, the scores from a second stable
    sort on the same key."""
    _, eidx = lax.top_k(chosen, k)
    _, s = lax.sort((-chosen, scores), dimension=1, num_keys=1, is_stable=True)
    return s[:, :k], eidx


def with_rule(form):
    """``form`` under the backward rule every gather-free form needs
    (``lax.sort``'s own JVP gathers): the cotangent of pick j of token
    n lands at ``[n, eidx[n, j]]`` by a compare and a sum over j."""

    @functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
    def picked(scores, chosen, k):
        return form(scores, chosen, k)

    def fwd(scores, chosen, k):
        out = form(scores, chosen, k)
        return out, (out[1], jnp.arange(chosen.shape[-1], dtype=jnp.int32))

    def bwd(k, res, cts):
        eidx, experts = res
        hit = eidx[:, :, None] == experts
        return jnp.sum(jnp.where(hit, cts[0][:, :, None], 0.0), axis=1), None

    picked.defvjp(fwd, bwd)
    return picked


FORMS = {
    "plain": plain,
    "sort3": with_rule(sort3),
    "sort3_int": with_rule(sort3_int),
    "select_sum": with_rule(select_sum),
    "two_sorts": with_rule(two_sorts),
}


def _route(form, logits, bias, k):
    scores = jax.nn.sigmoid(logits)
    return form(scores, scores + lax.stop_gradient(bias), k)


def _landed(logits, bias, k):
    """``router_topk`` itself, its product one with the identity (in
    the line's time; ``_landed_plain`` is the line to read it beside)."""
    eye = jnp.eye(logits.shape[-1], dtype=jnp.float32)
    return router_topk(logits, eye, k, False, scoring="sigmoid", select_bias=bias)[:2]


def _landed_plain(logits, bias, k):
    eye = jnp.eye(logits.shape[-1], dtype=jnp.float32)
    return _route(plain, logits @ eye, bias, k)


def _time(fn, *args):
    jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best / CHAIN * 1e3


def chain_ms(route, logits, bias, k, weights=None):
    """ms a call of ``route(logits + i * tiny, bias, k)``; with
    ``weights`` the call is the gradient of ``sum(gates ** 2 * weights)``
    to the logits, forward and backward (the square keeps the gates
    alive in it)."""

    def once(lg):
        if weights is None:
            gates, eidx = route(lg, bias, k)[:2]
            return gates.sum() + eidx.sum().astype(jnp.float32)
        return jax.grad(lambda x: jnp.sum(jnp.square(route(x, bias, k)[0]) * weights))(lg).sum()

    loop = jax.jit(lambda logits: lax.fori_loop(
        0, CHAIN,
        lambda i, acc: acc + once(logits + i.astype(jnp.float32) * 1e-6),
        jnp.zeros((), jnp.float32)))
    return _time(loop, logits)


def draw(n, e, k, seed, ties):
    """Logits, a bias and pick weights from ``seed``; ``ties``: logits
    rounded to quarters and no bias, so that every row has equal
    scores."""
    kl, kb, kw = jax.random.split(jax.random.key(seed), 3)
    logits = jax.random.normal(kl, (n, e), jnp.float32)
    bias = 0.1 * jax.random.normal(kb, (e,), jnp.float32)
    if ties:
        logits, bias = jnp.round(logits * 4) / 4, jnp.zeros_like(bias)
    return logits, bias, jax.random.normal(kw, (n, k), jnp.float32)


def equal_bits(route, want_route, logits, bias, k, weights):
    """Whether ``route`` gives ``want_route``'s gates, picks and
    gradient to the logits, bit for bit."""

    def both(r):
        def loss(x):
            gates, eidx = r(x, bias, k)[:2]
            return jnp.sum(jnp.square(gates) * weights), (gates, eidx)
        (_, (gates, eidx)), grad = jax.jit(jax.value_and_grad(loss, has_aux=True))(logits)
        return gates, eidx, grad

    return {name: bool(np.array_equal(np.asarray(a), np.asarray(b)))
            for name, a, b in zip(("gates", "eidx", "grad"), both(route), both(want_route))}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="chiprun_out/router_sweep.jsonl")
    ap.add_argument("--tokens", type=int, default=None,
                    help="rows in place of the cells' 16384 (a rehearsal off the chip)")
    args = ap.parse_args()
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    sink = open(args.out, "a")

    def emit(**line):
        print(json.dumps(line), flush=True)
        sink.write(json.dumps(line) + "\n")
        sink.flush()

    emit(device=str(jax.devices()[0].device_kind), n=len(jax.devices()))
    routes = {name: functools.partial(_route, form) for name, form in FORMS.items()}
    routes["landed_plain"], routes["landed"] = _landed_plain, _landed
    for n, e, k in SHAPES:
        n = args.tokens or n
        for name, route in routes.items():
            line = dict(form=name, shape=[n, e], k=k)
            # the product with the identity is the matrix unit's, not exact:
            # ``landed`` is held to the plain form behind the same product
            want = routes["landed_plain" if name.startswith("landed") else "plain"]
            try:
                for ties in (False, True):
                    logits, bias, weights = draw(n, e, k, 1, ties)
                    line["ties" if ties else "random"] = equal_bits(
                        route, want, logits, bias, k, weights)
                logits, bias, weights = draw(n, e, k, 0, False)
                emit(fwd_ms=chain_ms(route, logits, bias, k),
                     grad_ms=chain_ms(route, logits, bias, k, weights), **line)
            except Exception as err:  # a form the compiler refuses is a row of the table
                emit(error=str(err)[:300], **line)


if __name__ == "__main__":
    main()
