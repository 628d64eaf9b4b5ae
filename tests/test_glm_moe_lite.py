"""The ``glm4_moe_lite`` mechanisms of ``models/llama.py`` and
``parallel/moe.py`` — latent attention, a leading dense layer, a
sigmoid router with a selection bias, a shared expert, a HELD range of
the routed experts (one expert-parallel rank's share by itself), a
multi-token-prediction module — against the plain reference
``benchmark/reference/glm_moe_lite.py`` on seeded weights, small
widths, float32, on the CPU.

Tolerances: program and reference are both float32 here and differ in
the order of their sums (the program gathers sorted rows into grouped
products, the reference multiplies a dense gate matrix; flash's
reference math against a per-head softmax), so losses agree to 1e-6
relative and gradients to 1e-4 of a leaf's largest entry (the worst
leaf read 3.3e-5 when this was written).  bfloat16 in place of
float32 moves the loss by 1e-3 and a gradient by 1e-2; a softmax in
place of the sigmoid, a missing 1.8, a missing shared expert, a bias
inside the gates or an unshifted MTP label move the loss by 1e-2 or
more (``test_a_wrong_mechanism_fails``).
"""

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from benchmark.reference import glm_moe_lite as ref
from benchmark.run import program_knobs
from test_flash_remat import _eqns
from test_gqa_proj import rope_stride2
from theanompi_tpu.models.llama import Llama, _heads, rms_norm, rope_tail
from theanompi_tpu.parallel import make_mesh, moe
from theanompi_tpu.parallel import tp as tp_lib
from theanompi_tpu.utils import Recorder

LOSS_RTOL = 1e-6
GRAD_TOL = 1e-4

CONFIG = json.loads(
    (Path(__file__).parents[1] / "benchmark" / "configs"
     / "glm_4.7_flash_train_ep8_l5.json").read_text()
)


def rehearsal(**over):
    """The cell's configuration at its rehearsal sizes: the program's
    knobs and the reference's arguments."""
    config = dict(CONFIG, **CONFIG["rehearsal"])
    knobs = dict(program_knobs(config), optimizer="sgd", lr=1.0,
                 n_train=8, n_val=1, seed=3)
    knobs.update(over)
    return knobs, dict(config["reference"]["kwargs"])


def build(knobs, **layout):
    n = int(np.prod(list(layout.values()) or [1]))
    model = Llama(dict(knobs, tp=layout.get("model", 1)))
    n_dp = layout.get("data", 1)
    model.build_model(n_replicas=n_dp)
    model.compile_iter_fns(
        mesh=make_mesh(devices=jax.devices()[:n], **layout))
    return model


def one_step(model, batch=None):
    """One SGD step at lr 1 on ``batch`` (default: the first):
    ``(loss, the parameters' change = the gradient, routing counters,
    the bias after the step)``."""
    before = jax.device_get(model.params)
    x, y = model.put_batch(batch or model.data.train_batch(0))
    out = model._train_step(
        model.params, model.opt_state, model.ef_state, x, y,
        jnp.float32(1.0), *model._state_args())
    model.params, model.opt_state, model.ef_state = out[:3]
    loss, _, routing, *_ = model._take_state(out[3:])
    grads = jax.tree.map(
        lambda a, b: np.asarray(a) - np.asarray(b), before,
        jax.device_get(model.params))
    return float(loss), grads, np.asarray(routing), before


def assert_grads_close(got, want, tol=GRAD_TOL):
    flat_got = jax.tree_util.tree_flatten_with_path(got)[0]
    flat_want = jax.tree.leaves(want)
    assert len(flat_got) == len(flat_want)
    for (path, g), w in zip(flat_got, flat_want):
        w = np.asarray(w)
        scale = np.abs(w).max()
        if "router" not in jax.tree_util.keystr(path):
            assert scale > 0, jax.tree_util.keystr(path)
        assert np.abs(g - w).max() <= tol * scale, (
            jax.tree_util.keystr(path), np.abs(g - w).max() / scale)


# -- the whole step against the reference ------------------------------------


@pytest.fixture(scope="module")
def stepped():
    knobs, kw = rehearsal()
    model = build(knobs)
    batch = model.data.train_batch(0)
    bias0 = np.asarray(model.net_state["moe_bias"])
    loss, grads, routing, before = one_step(model, batch)
    return dict(model=model, batch=batch, kw=kw, loss=loss, grads=grads,
                routing=routing, before=before, bias0=bias0,
                picks=batch[0].size * knobs["moe_top_k"])


def test_step_loss_and_every_gradient_match_the_reference(stepped):
    """The rehearsal preset's whole step — the dense layer, two expert
    layers with 2 of 8 experts held, the MTP module, the sliced
    vocabulary — loss and every leaf's gradient."""
    x, y = (jnp.asarray(a) for a in stepped["batch"])
    (want, counts), want_grads = jax.value_and_grad(
        lambda p: ref.loss_and_counts(
            p, x, y, bias=jnp.asarray(stepped["bias0"]), **stepped["kw"]),
        has_aux=True,
    )(stepped["before"])
    assert abs(stepped["loss"] - float(want)) <= LOSS_RTOL * float(want)
    assert_grads_close(stepped["grads"], want_grads)
    # the counters: the picks of every expert ROUTED over, a layer
    np.testing.assert_array_equal(
        np.rint(stepped["routing"][:, :-1] * stepped["picks"]),
        np.asarray(counts))
    assert not stepped["routing"][:, -1].any()      # dropless


def test_every_new_leaf_has_a_gradient(stepped):
    layer = stepped["grads"]["layers"][1]
    for name in ("wq_a", "q_a_norm", "wq_b", "wkv_a", "kv_a_norm", "wkv_b",
                 "ws_gate", "ws_up", "ws_down", "we_gate"):
        assert np.abs(layer[name]).max() > 0, name
    mtp = stepped["grads"]["mtp"]
    for name in ("enorm", "hnorm", "eh_proj", "head_norm"):
        assert np.abs(mtp[name]).max() > 0, name


def test_a_share_by_itself_holds_its_router(stepped):
    """2 of 8 experts held: the gates' gradient would come back from
    those two alone and pull every pick towards them, so the routers
    of such a share get exactly none (program and reference alike,
    the test above); with all 8 held the same layer's router gets
    its gradient as ever."""
    grads = stepped["grads"]
    for block in (*grads["layers"][1:], grads["mtp"]["block"]):
        assert not np.asarray(block["router"]).any()
        assert np.abs(block["we_gate"]).max() > 0
    knobs, _ = rehearsal()
    whole = build(dict(knobs, moe_experts_held=knobs["n_experts"]))
    _, whole_grads, _, _ = one_step(whole, stepped["batch"])
    assert np.abs(whole_grads["layers"][1]["router"]).max() > 0
    assert "w_gate" in stepped["grads"]["layers"][0]       # the dense layer
    assert stepped["grads"]["layers"][1]["we_gate"].shape[0] == 2   # held


def test_bias_moves_by_the_sign_of_the_load_error_and_only_so(stepped):
    model = stepped["model"]
    got = np.asarray(model.net_state["moe_bias"])
    counts = np.rint(stepped["routing"][:, :-1] * stepped["picks"])
    want = np.asarray(ref.bias_update(stepped["bias0"], counts, 0.001))
    np.testing.assert_array_equal(got, want)
    moved = got - stepped["bias0"]
    assert set(np.unique(np.abs(moved))) <= {np.float32(0.0),
                                             np.float32(0.001)}
    mean = counts.mean(axis=1, keepdims=True)
    np.testing.assert_array_equal(np.sign(moved), np.sign(mean - counts))


def test_bias_is_no_parameter_and_has_no_optimizer_state():
    knobs, _ = rehearsal(optimizer="adam", lr=1e-3)
    model = build(knobs)
    names = {jax.tree_util.keystr(p)
             for p, _ in jax.tree_util.tree_flatten_with_path(model.params)[0]}
    assert not any("bias" in n for n in names), names
    assert (jax.tree.structure(model.opt_state["m"])
            == jax.tree.structure(model.params))
    assert model.net_state["moe_bias"].shape == (3, 8)     # 2 layers + MTP
    assert model.moe_calls == 3


@pytest.mark.parametrize("wrong", [
    dict(moe_scoring="softmax"), dict(moe_route_scale=1.0),
    dict(moe_shared_experts=0), dict(mtp_coef=0.0),
    dict(compute_dtype="bfloat16"),
], ids=str)
def test_a_wrong_mechanism_fails(stepped, wrong):
    """The tolerance is tight enough: each of these builds moves the
    first loss off the reference's by far more than ``LOSS_RTOL``."""
    knobs, _ = rehearsal(**wrong)
    model = build(knobs)
    if "ws_gate" in stepped["before"]["layers"][1] and (
            wrong.get("moe_shared_experts") != 0):
        model.params = jax.device_put(
            stepped["before"], model._shardings(model._specs))
    loss, *_ = one_step(model, stepped["batch"])
    assert abs(loss - stepped["loss"]) > 100 * LOSS_RTOL * stepped["loss"]


# -- single mechanisms -------------------------------------------------------


def _in_shard_map(model, fn, *args):
    mesh = make_mesh(devices=jax.devices()[:1])
    return jax.jit(jax.shard_map(
        fn, mesh=mesh, in_specs=(P(),) * len(args), out_specs=P(),
    ))(*args)


def _small_layer(**over):
    knobs, kw = rehearsal(**over)
    model = Llama(knobs)
    params = model._init_full_params(jax.random.key(5))
    x = jax.random.normal(jax.random.key(6), (1, 32, model.dim), jnp.float32)
    return model, params, x, kw


def test_mla_layer_forward_and_gradients():
    """One dense block with latent attention, alone."""
    model, params, x, kw = _small_layer()
    lp = params["layers"][0]
    pos = jnp.arange(x.shape[1])

    def program(lp, x):
        return _in_shard_map(
            model, lambda lp, x: jnp.sum(
                jnp.sin(model._layer(lp, x, pos))), lp, x)

    def reference(lp, x):
        with jax.default_matmul_precision("highest"):
            return jnp.sum(jnp.sin(ref.layer(x[0], lp, **kw)[0]))

    got, got_g = jax.value_and_grad(program, argnums=(0, 1))(lp, x)
    want, want_g = jax.value_and_grad(reference, argnums=(0, 1))(lp, x)
    assert abs(float(got) - float(want)) <= 1e-5 * abs(float(want)) + 1e-5
    assert_grads_close(jax.device_get(got_g), want_g)


def _mla_qkv_sliced(model, p, xn, pos):
    """The oracle: latent attention's projections as ``_mla_qkv`` built
    them until PR 38, by slicing, rotating and re-joining the
    ACTIVATIONS — ``wkv_b``'s product one ``[.., nope + v]`` array cut
    at ``nope``, RoPE on a slice of q, the rotary key broadcast over
    the heads and concatenated in."""
    eps, theta = model.norm_eps, model.rope_theta
    h, nope, rank = model.n_heads, model.qk_nope_head_dim, model.kv_lora_rank
    cq = rms_norm(xn @ p["wq_a"].astype(xn.dtype), p["q_a_norm"], eps)
    q = _heads(cq @ p["wq_b"].astype(xn.dtype), h, model.head_dim)
    q = jnp.concatenate(
        [q[..., :nope], rope_stride2(q[..., nope:], pos, theta)], axis=-1)
    ckv = xn @ p["wkv_a"].astype(xn.dtype)
    k_rope = rope_stride2(ckv[:, None, :, rank:], pos, theta)
    ckv = rms_norm(ckv[..., :rank], p["kv_a_norm"], eps)
    kv = _heads(ckv @ p["wkv_b"].astype(xn.dtype), h, nope + model.v_head_dim)
    k = jnp.concatenate([
        kv[..., :nope],
        jnp.broadcast_to(k_rope, (*kv.shape[:3], model.qk_rope_head_dim)),
    ], axis=-1)
    return q, k, kv[..., nope:]


_MLA_LEAVES = ("wq_a", "q_a_norm", "wq_b", "wkv_a", "kv_a_norm", "wkv_b")


@pytest.mark.parametrize("dtype, tol", [("float32", GRAD_TOL),
                                        ("bfloat16", 2e-2)])
def test_mla_operands_equal_the_sliced_form(dtype, tol):
    """``_mla_qkv`` writes the kernels' operands from products over
    cuts of the WEIGHTS; the sliced form above is the same
    mathematics.  q, k, v and, under random cotangents, the gradients
    of ``xn`` and of all six leaves; in bfloat16 k's rotary part (one
    input times 1, accumulated in float32) and v (the same product,
    column for column) equal the oracle's bit for bit."""
    model, params, x, _ = _small_layer(compute_dtype=dtype)
    lp = {name: params["layers"][0][name] for name in _MLA_LEAVES}
    xn = x.astype(dtype)
    pos = jnp.arange(x.shape[1])
    cts = [jax.random.normal(jax.random.key(7 + i),
                             (1, model.n_heads, x.shape[1], model.head_dim))
           for i in range(3)]

    def run(form):
        def scalar(lp, xn):
            qkv = form(lp, xn)
            total = sum(jnp.sum(a.astype(jnp.float32) * c)
                        for a, c in zip(qkv, cts))
            return total, qkv
        return _in_shard_map(
            model, lambda lp, xn: jax.value_and_grad(
                scalar, argnums=(0, 1), has_aux=True)(lp, xn), lp, xn)

    (_, got), got_g = run(lambda lp, xn: model._mla_qkv(lp, xn, pos))
    (_, want), want_g = run(
        lambda lp, xn: _mla_qkv_sliced(model, lp, xn, pos))
    nope = model.qk_nope_head_dim
    for name, g, w in zip("qkv", got, want):
        assert g.shape == w.shape and g.dtype == w.dtype == jnp.dtype(dtype)
        g, w = (np.asarray(a, np.float32) for a in (g, w))
        assert np.abs(g - w).max() <= tol * np.abs(w).max(), name
    if dtype == "bfloat16":
        np.testing.assert_array_equal(
            np.asarray(got[1], np.float32)[..., nope:],
            np.asarray(want[1], np.float32)[..., nope:])
        np.testing.assert_array_equal(
            np.asarray(got[2], np.float32), np.asarray(want[2], np.float32))
    assert set(got_g[0]) == set(_MLA_LEAVES)
    as_f32 = lambda tree: jax.tree.map(
        lambda a: np.asarray(a, np.float32), jax.device_get(tree))
    assert_grads_close(as_f32(got_g), as_f32(want_g), tol)


@pytest.mark.parametrize("nope", [0, 12, 16])
def test_rope_tail_is_rope_on_the_tail(nope):
    """One pass over the whole row against the stride-2 rotation
    (``rope`` as it was until PR 45: the oracle of
    ``tests/test_gqa_proj.py``) on a slice, joined back: values, and
    the gradient — the rotation by the negative angle — against
    autodiff of the sliced form."""
    x = jax.random.normal(jax.random.key(11), (2, 3, 8, 16), jnp.float32)
    ct = jax.random.normal(jax.random.key(12), x.shape, jnp.float32)
    pos = jnp.arange(5, 13)

    def sliced(x):
        return jnp.concatenate(
            [x[..., :nope], rope_stride2(x[..., nope:], pos, 1e4)], axis=-1)

    got, got_vjp = jax.vjp(lambda x: rope_tail(x, pos, 1e4, nope), x)
    want, want_vjp = jax.vjp(sliced, x)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(got[..., :nope], x[..., :nope])
    np.testing.assert_allclose(
        got_vjp(ct)[0], want_vjp(ct)[0], rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("tp", [1, 2])
def test_col_parallel_heads_is_col_parallel_then_heads(tp):
    """``tp.col_parallel_heads`` against ``col_parallel`` and the head
    transpose, under the vma-checked ``shard_map`` the step uses:
    values and both gradients (``x`` replicated over the model axis,
    ``w`` split by whole heads)."""
    heads, hd = 4, 8
    x = jax.random.normal(jax.random.key(13), (2, 16, 24), jnp.float32)
    w = jax.random.normal(jax.random.key(14), (24, heads * hd), jnp.float32)
    ct = jax.random.normal(jax.random.key(15), (2, heads, 16, hd))
    mesh = make_mesh(devices=jax.devices()[:tp], model=tp)
    col = P(None, "model")

    def run(form):
        def scalar(x, w, ct):
            return jax.lax.psum(jnp.sum(form(x, w) * ct), "model")
        return jax.jit(jax.shard_map(
            jax.value_and_grad(scalar, argnums=(0, 1)), mesh=mesh,
            in_specs=(P(), col, P(None, "model")),
            out_specs=(P(), (P(), col)),
        ))(x, w, ct)

    got, got_g = run(
        lambda x, w: tp_lib.col_parallel_heads(x, w, heads // tp))
    want, want_g = run(
        lambda x, w: _heads(tp_lib.col_parallel(x, w), heads // tp, hd))
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for g, w_ in zip(got_g, want_g):
        np.testing.assert_allclose(g, w_, rtol=1e-5, atol=1e-5)


def test_one_rotary_key_for_all_heads():
    """``k``'s rotary part is the same vector in every head."""
    model, params, x, _ = _small_layer()
    pos = jnp.arange(x.shape[1])
    _, k, _ = _in_shard_map(
        model, lambda lp, x: model._mla_qkv(lp, x, pos),
        params["layers"][0], x)
    rope_part = np.asarray(k)[..., model.qk_nope_head_dim:]
    assert np.abs(rope_part).max() > 0
    np.testing.assert_array_equal(rope_part, rope_part[:, :1].repeat(
        model.n_heads, axis=1))


def test_sigmoid_router_matches_the_reference():
    """Selection by ``s + b``, gates from ``s`` alone, renormalised,
    times 1.8 (a tie-free seed: the scores' gaps are far above one
    float32 step)."""
    rng = np.random.default_rng(11)
    h = rng.standard_normal((64, 16)).astype(np.float32)
    w = rng.standard_normal((16, 8)).astype(np.float32)
    bias = (0.3 * rng.standard_normal(8)).astype(np.float32)
    gates, idx, scores, _ = moe.router_topk(
        jnp.asarray(h), jnp.asarray(w), 3, True, scoring="sigmoid",
        select_bias=jnp.asarray(bias), scale=1.8)
    with jax.default_matmul_precision("highest"):
        want_gate, want_idx, want_s = ref.route(
            jnp.asarray(h), w, bias, 3, 1.8)
    np.testing.assert_array_equal(np.sort(idx, -1), np.sort(want_idx, -1))
    dense = np.zeros((64, 8), np.float32)
    np.put_along_axis(dense, np.asarray(idx), np.asarray(gates), -1)
    np.testing.assert_allclose(dense, want_gate, rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(gates.sum(-1), 1.8, rtol=1e-6)
    # the bias chooses and is not in the gates: without it other picks
    _, plain_idx, _, _ = moe.router_topk(
        jnp.asarray(h), jnp.asarray(w), 3, True, scoring="sigmoid")
    assert (np.sort(plain_idx, -1) != np.sort(idx, -1)).any()
    np.testing.assert_allclose(
        np.take_along_axis(np.asarray(scores), np.asarray(idx), -1)
        / np.take_along_axis(np.asarray(scores), np.asarray(idx), -1)
        .sum(-1, keepdims=True) * 1.8, gates, rtol=1e-6)


def test_the_shares_add_up():
    """E = 8 routed experts in 4 shares of 2: the routed parts the
    four shares give, plus the shared expert's output and the
    residual counted ONCE, equal what the uncut reference gives for
    the whole layer.  A share holds experts ``[0, held)``; share r is
    asked for by rolling the router's columns (and the bias) so that
    its experts come first — the same scores, the same picks, the
    same gates under other names."""
    rng = np.random.default_rng(2)
    n, d, f, e, k = 48, 16, 8, 8, 3
    x = jnp.asarray(rng.standard_normal((1, n, d)), jnp.float32)
    lp = {
        "mlp_norm": jnp.ones((d,)),
        "router": jnp.asarray(rng.standard_normal((d, e)), jnp.float32),
        "we_gate": jnp.asarray(rng.standard_normal((e, d, f)) / 4, jnp.float32),
        "we_up": jnp.asarray(rng.standard_normal((e, d, f)) / 4, jnp.float32),
        "we_down": jnp.asarray(rng.standard_normal((e, f, d)) / 4, jnp.float32),
        "ws_gate": jnp.asarray(rng.standard_normal((d, f)) / 4, jnp.float32),
        "ws_up": jnp.asarray(rng.standard_normal((d, f)) / 4, jnp.float32),
        "ws_down": jnp.asarray(rng.standard_normal((f, d)) / 4, jnp.float32),
    }
    bias = jnp.asarray(0.2 * rng.standard_normal(e), jnp.float32)
    with jax.default_matmul_precision("highest"):
        whole, shared, routed, counts = ref.ffn(
            x[0], lp, bias, top_k=k, scale=1.8, eps=1e-5)
    h = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-5)

    def share(r, h):
        lo = 2 * r
        y, aux = moe.moe_ffn(
            h, jnp.roll(lp["router"], -lo, axis=1),
            *(lp[name][lo:lo + 2] for name in ("we_gate", "we_up", "we_down")),
            n_experts=e, top_k=k, capacity_factor=None, expert_axis=None,
            model_axis=None, scoring="sigmoid",
            select_bias=jnp.roll(bias, -lo), route_scale=1.8, held=2)
        return y[0], jnp.roll(aux["f"], lo)

    parts, fs = zip(*(share(r, h) for r in range(4)))
    for f_share in fs:          # every share counts the picks of all 8
        np.testing.assert_allclose(f_share * n * k, counts, atol=1e-4)
    np.testing.assert_allclose(sum(parts), routed, rtol=2e-5, atol=2e-6)
    got = x[0] + moe.shared_expert(
        h[0], lp["ws_gate"], lp["ws_up"], lp["ws_down"], None) + sum(parts)
    np.testing.assert_allclose(got, x[0] + whole, rtol=2e-5, atol=2e-6)
    # and a share's rows not held add exactly nothing, backward too
    # (the gates of a share by itself scale the rows and carry no
    # gradient to the scores: ``ref.ffn``)
    g = jax.grad(lambda h: jnp.sum(share(0, h)[0] ** 2))(h)
    with jax.default_matmul_precision("highest"):
        want_g = jax.grad(lambda h: jnp.sum(ref.routed(
            h[0], jax.lax.stop_gradient(
                ref.route(h[0], lp["router"], bias, k, 1.8)[0][:, :2]),
            {n_: lp[n_][:2] for n_ in ("we_gate", "we_up", "we_down")},
        ) ** 2))(h)
    np.testing.assert_allclose(g, want_g, rtol=1e-4, atol=1e-5)


def test_mtp_labels_are_the_token_after_next_and_the_last_weighs_nothing():
    knobs, _ = rehearsal()
    model = Llama(knobs)
    rng = np.random.default_rng(4)
    b, t, d, v = 2, 8, model.dim, model.vocab
    exits = jnp.asarray(rng.standard_normal((2, b * t, d)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((d, v)) / 4, jnp.float32)
    y = rng.integers(0, v, (b, t)).astype(np.int32)

    def loss(y):
        return _in_shard_map(
            model, lambda e, w, y: model._mtp_loss({"lm_head": w}, e, y)[0],
            exits, w, jnp.asarray(y))

    logp = jax.nn.log_softmax(exits @ w, -1)
    main = -np.take_along_axis(np.asarray(logp[0]), y.reshape(-1, 1), 1)
    after = -np.take_along_axis(
        np.asarray(logp[1]).reshape(b, t, v)[:, :-1],
        y[:, 1:, None], 2)          # position i against y[i + 1] = t_{i+2}
    want = main.mean() + model.mtp_coef * after.sum() / (b * t)
    np.testing.assert_allclose(float(loss(y)), want, rtol=1e-6)
    # the label a sequence's last position would be held to counts
    # only in the main loss; y[:, 0] is no MTP label at all
    moved = y.copy()
    moved[:, 0] = (moved[:, 0] + 1) % v
    main_moved = -np.take_along_axis(
        np.asarray(logp[0]), moved.reshape(-1, 1), 1).mean()
    np.testing.assert_allclose(
        float(loss(moved)) - float(loss(y)), main_moved - main.mean(),
        rtol=1e-4, atol=1e-7)


# -- the bias as state -------------------------------------------------------


def test_bias_is_the_same_on_both_replicas_of_dp2_and_equals_dp1():
    knobs, _ = rehearsal(batch_size=1, n_train=8)
    two = build(knobs, data=2)
    one = build(dict(knobs, batch_size=2))
    batch = one.data.train_batch(0)
    one_step(two, batch)
    one_step(one, batch)
    bias = two.net_state["moe_bias"]
    shards = [np.asarray(s.data) for s in bias.addressable_shards]
    assert len(shards) == 2
    np.testing.assert_array_equal(shards[0], shards[1])
    assert np.abs(shards[0]).max() == np.float32(0.001)
    np.testing.assert_array_equal(
        shards[0], np.asarray(one.net_state["moe_bias"]))


def test_bias_survives_a_checkpoint_round_trip(tmp_path):
    knobs, _ = rehearsal(optimizer="adam", lr=1e-3)
    model = build(knobs)
    for _ in range(3):
        one_step(model)
    want = np.asarray(model.net_state["moe_bias"])
    assert np.abs(want).max() > 0.001
    model.save(str(tmp_path), Recorder())
    fresh = build(knobs)
    assert not np.asarray(fresh.net_state["moe_bias"]).any()
    assert fresh.load(str(tmp_path))
    np.testing.assert_array_equal(
        np.asarray(fresh.net_state["moe_bias"]), want)
    # and a load BEFORE the step is compiled keeps it too
    early = Llama(knobs)
    early.build_model(n_replicas=1)
    assert early.load(str(tmp_path))
    early.compile_iter_fns(mesh=make_mesh(devices=jax.devices()[:1]))
    np.testing.assert_array_equal(
        np.asarray(early.net_state["moe_bias"]), want)


def test_bias_balances_a_skewed_router():
    """A selection that starts far off balance (experts 0 and 1 take
    every pick); 200 steps of the rule alone (lr 0: nothing else
    moves) bring ``moe_load_max_over_mean`` down."""
    knobs, _ = rehearsal(lr=0.0, moe_bias_rate=0.01, mtp_depth=0,
                         device_data_cache=True, steps_per_call=2)
    model = build(knobs)
    skew = np.zeros((2, 8), np.float32)
    skew[:, :2] = 0.5
    model.net_state = jax.device_put(
        {"moe_bias": skew}, model._shardings(model._state_specs[0]))
    recorder = Recorder()
    model.train_chunk(0, 2, recorder)
    recorder.flush()
    first = recorder.moe_counters["moe_load_max_over_mean"]
    for i in range(1, 100):
        model.train_chunk(2 * i, 2, recorder)
    recorder.flush()
    last = recorder.moe_counters
    assert first > 3.0, first
    assert last["moe_load_max_over_mean"] < 0.6 * first, (first, last)
    assert 0.05 < last["moe_bias_abs_max"] <= 0.5 + 200 * 0.01 + 1e-6
    assert last["moe_experts_held"] == 2
    assert len(last["moe_rows_held"]) == 2
    assert last["moe_rows_held"] == [
        sum(row[:2]) for row in last["moe_rows_per_expert"]]


# -- layouts -----------------------------------------------------------------


def test_tp2_composes(stepped):
    """Heads over the ``model`` axis, the two latent down-projections
    and their norms replicated; the experts' and the shared expert's
    width, the dense layer's width and the vocabulary sharded: loss
    and every leaf's gradient equal one device's."""
    knobs, _ = rehearsal()
    model = build(knobs, model=2)
    loss, grads, routing, _ = one_step(model, stepped["batch"])
    assert abs(loss - stepped["loss"]) <= 2e-6 * stepped["loss"]
    assert_grads_close(grads, stepped["grads"])
    np.testing.assert_array_equal(routing, stepped["routing"])


@pytest.mark.parametrize("layout", [dict(), dict(model=2), dict(data=2)],
                         ids=str)
def test_step_over_the_bounded_rows_equals_the_full_length_step(
    layout, monkeypatch
):
    """48 tokens x 2 picks, 2 of 8 experts held: the expert calls lay
    out 48 of their 96 sorted rows (24 of 48 a data shard) under the
    step's scan, remat and checked ``shard_map``.  Loss, every leaf's
    gradient and the counters equal those of the same layout's step
    whose bound is all its rows."""
    knobs, _ = rehearsal(seq_len=24)
    assert moe.held_rows_bound(96, 2, 8) == 48
    assert moe.held_rows_bound(48, 2, 8) == 24
    model = build(knobs, **layout)
    batch = model.data.train_batch(0)
    loss, grads, routing, _ = one_step(model, batch)
    monkeypatch.setattr(moe, "held_rows_bound", lambda picks, *_: picks)
    want_loss, want, want_routing, _ = one_step(
        build(knobs, **layout), batch)
    assert abs(loss - want_loss) <= 2e-6 * want_loss
    assert_grads_close(grads, want)
    np.testing.assert_array_equal(routing, want_routing)


@pytest.mark.parametrize("layout", [dict(pp=2), dict(sp=2),
                                    dict(ut_steps=2)], ids=str)
def test_layouts_it_does_not_compose_with_are_refused(layout):
    knobs, _ = rehearsal(n_layers=4, **layout)
    with pytest.raises(NotImplementedError, match="does not yet compose"):
        Llama(knobs)


def test_held_range_needs_the_dropless_path():
    knobs, _ = rehearsal(capacity_factor=1.25)
    with pytest.raises(NotImplementedError, match="moe_experts_held"):
        Llama(knobs)


def test_held_range_on_a_tpu_needs_shapes_the_kernels_tile(monkeypatch):
    """Off the TPU ``lax.ragged_dot`` leaves the rows past its groups
    zero; XLA's kernels on the chip do not (PERF.md, PR 37), so a held
    range there is the repo's kernels' or refused — no masked second
    path."""
    from theanompi_tpu.ops import attention

    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    sizes = jnp.array([3, 2], jnp.int32)
    with pytest.raises(NotImplementedError, match="grouped kernels tile"):
        moe._grouped_product(sizes, 24, 16, 8, jnp.float32, prefix=True)
    # all rows in groups: the fallback as ever
    assert callable(moe._grouped_product(sizes, 24, 16, 8, jnp.float32))


def test_unequal_qk_and_value_rows_are_refused():
    knobs, _ = rehearsal(v_head_dim=8)
    with pytest.raises(NotImplementedError, match="one head dim"):
        Llama(knobs)


def test_remat_counts_the_calls_that_are_dense():
    knobs, _ = rehearsal()
    model = Llama(knobs)
    assert model.layer_kinds == ("dense", "moe", "moe")
    per_call = model.remat_kept_bytes_per_call
    assert per_call == 2 * 2 * 32 * 96 * 4      # the DENSE width, fp32
    # never an expert call for the MLP's names; latent attention names
    # nothing; the two expert calls their own
    assert model.remat_keep_calls(1 << 40) == (1, 0, 2)
    model.remat_kept_calls, model.remat_kept_moe_calls = 1, 1
    assert model._kept_calls() == ({0}, set(), {2})
    assert model.remat_saves[-1] == "moe_tile_plan"
    # grouped-query attention's names are every layer's, dense or not
    plain = Llama(dict(n_layers=3, n_experts=4, capacity_factor=None))
    assert plain.remat_keep_calls(1 << 40) == (0, 3, 3)
    assert Llama(dict(n_layers=3)).remat_keep_calls(1 << 40) == (3, 3, 0)


def test_mtp_block_is_the_stacks_own_layer_call():
    """The MTP block runs the SAME checkpointed call as the stack's
    expert layers (one trace, one private function of the lowered
    text, as before the calls were built by what they keep): of the
    step's four layer calls under the remat (dense, two expert, the
    MTP block's) two are distinct."""
    knobs, _ = rehearsal()
    model = build(knobs)
    x, y = model.put_batch(model.data.train_batch(0))
    jaxpr = jax.make_jaxpr(model._train_step)(
        model.params, model.opt_state, model.ef_state, x, y,
        jnp.float32(1.0), *model._state_args())

    calls = [eqn.params["jaxpr"] for eqn in _eqns(jaxpr.jaxpr)
             if eqn.primitive.name == "remat2"]
    assert (len(calls), len({id(call) for call in calls})) == (4, 2)


def test_summary_names_the_mechanisms():
    from theanompi_tpu import BSP

    knobs, _ = rehearsal(optimizer="adam", lr=1e-3, n_epochs=1,
                         device_data_cache=True, steps_per_call=2)
    rule = BSP()
    rule.init(devices=[0], modelfile="theanompi_tpu.models.llama",
              modelclass="Llama", launch="inprocess", config=knobs,
              verbose=False)
    res = rule.wait()
    assert res["attention"] == "mla"
    assert res["experts_held"] == 2 and res["mtp_depth"] == 1
    counters = res["moe_counters"]
    assert counters["moe_experts_held"] == 2
    assert len(counters["moe_rows_held"]) == 3
    assert 0 < counters["moe_bias_abs_max"] <= 0.004 + 1e-7
