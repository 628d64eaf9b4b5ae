"""The ``laguna`` mechanisms of ``models/llama.py`` and
``parallel/moe.py`` — query heads a LAYER over one set of key/value
heads, a sigmoid gate a head on attention's output, a rotary width a
layer kind (half a head under YaRN on the full layers), a routed
scaling factor on the softmax router, a shared expert beside it, a
dense first layer in a window/full stack, a window as wide as the
kernels' row block — against the plain reference
``benchmark/reference/laguna_moe.py`` on seeded weights, small widths,
float32, on the CPU.

Tolerances: as ``tests/test_mellum_moe.py``'s and for its reasons —
program and reference are both float32 here and differ in the order of
their sums, so losses agree to 1e-6 relative and gradients to 1e-4 of
a leaf's largest entry.  Each of the seven wrong mechanisms the issue
names moves the loss by more than a hundred times the first or a
leaf's gradient by more than a hundred times the second
(``test_a_wrong_mechanism_fails``).
"""

import json
import math
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import flops
from benchmark.reference import laguna_moe as ref
from benchmark.run import program_knobs
from benchmark.tools.laguna_check import _fitted
from theanompi_tpu.models.llama import Llama, rope, rope_table
from theanompi_tpu.ops import attention
from theanompi_tpu.parallel import make_mesh, moe

LOSS_RTOL = 1e-6
GRAD_TOL = 1e-4

ROOT = Path(__file__).parents[1]
CONFIG = json.loads(
    (ROOT / "benchmark/configs/laguna_s_2.1_train_ep32_l5.json").read_text())
PUBLISHED = CONFIG["published"]


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
    model.build_model(n_replicas=layout.get("data", 1))
    model.compile_iter_fns(
        mesh=make_mesh(devices=jax.devices()[:n], **layout))
    return model


def one_step(model, batch, start=None):
    """One step (SGD at lr 1) on ``batch`` from ``start`` (default:
    the model's own weights): ``(loss, the parameters' change = the
    gradient, routing counters, gate counters, the parameters
    before)``."""
    if start is not None:
        # (a build without the gate or the shared expert holds fewer
        # leaves: the right weights less those)
        model.params = jax.device_put(
            _fitted(start, model.param_specs()),
            model._shardings(model._specs))
    before = jax.device_get(model.params)
    x, y = model.put_batch(batch)
    out = model._train_step(
        model.params, model.opt_state, model.ef_state, x, y,
        jnp.float32(1.0))
    loss, _, routing, *gate = out[3:]
    grads = jax.tree.map(
        lambda a, b: np.asarray(a) - np.asarray(b), before,
        jax.device_get(out[0]))
    return (float(loss), grads, np.asarray(routing),
            np.asarray(gate[0]) if gate else None, before)


def worst_gradient_gap(got, want, before) -> tuple[float, str]:
    """The largest difference of a leaf of ``got`` over that leaf's
    largest entry of ``want``, and the leaf (half an ulp of the
    parameter is the subtraction's, ``tests/test_mellum_moe.py``)."""
    want = dict(jax.tree_util.tree_flatten_with_path(want)[0])
    before = dict(jax.tree_util.tree_flatten_with_path(before)[0])
    worst = (0.0, "")
    for path, g in jax.tree_util.tree_flatten_with_path(got)[0]:
        w = np.asarray(want[path])
        scale = np.abs(w).max()
        assert scale > 0, jax.tree_util.keystr(path)
        ulp = np.finfo(np.float32).eps * np.abs(before[path]).max()
        worst = max(worst, (
            float(max(np.abs(g - w).max() - ulp, 0.0) / scale),
            jax.tree_util.keystr(path)))
    return worst


def reference_step(params, batch, kw):
    x, y = (jnp.asarray(a) for a in batch)
    (loss, counts), grads = jax.value_and_grad(
        lambda p: ref.loss_and_counts(p, x, y, **kw), has_aux=True)(params)
    return float(loss), grads, np.asarray(counts)


# -- the whole step against the reference ------------------------------------


def _stepped(**over):
    knobs, kw = rehearsal(**over)
    model = build(knobs)
    batch = model.data.train_batch(0)
    loss, grads, routing, gate, before = one_step(model, batch)
    return dict(model=model, batch=batch, kw=kw, loss=loss, grads=grads,
                routing=routing, gate=gate, before=before,
                want=reference_step(before, batch, kw),
                picks=batch[0].size * knobs["moe_top_k"])


@pytest.fixture(scope="module")
def stepped():
    """2 of 8 experts held: one rank's share."""
    return _stepped()


@pytest.fixture(scope="module")
def stepped_whole():
    """All 8 experts held: the gates carry their gradient."""
    return _stepped(moe_experts_held=8)


def test_the_rehearsal_has_every_mechanism():
    knobs, kw = rehearsal()
    model = Llama(knobs)
    assert model.attn_kinds == (
        "full_attention", "sliding_attention", "sliding_attention",
        "sliding_attention", "full_attention")
    assert model.heads_per_layer == (4, 6, 6, 6, 4)
    assert model.layer_kinds == ("dense", "moe", "moe", "moe", "moe")
    assert model.attention_gate and model.moe_shared_experts == 1
    assert model.rotary_channels == {
        "full_attention": 8, "sliding_attention": 16}
    assert model.moe_route_scale == 2.5 and model.moe_scoring == "softmax"
    assert model.sliding_window < model.seq_len
    assert model.moe_experts_held < model.n_experts
    assert kw["layer_types"] == list(model.attn_kinds)
    assert kw["heads_per_layer"] == list(model.heads_per_layer)


@pytest.mark.parametrize("which", ["stepped", "stepped_whole"])
def test_step_loss_and_every_gradient_match_the_reference(which, request):
    """The rehearsal preset's whole step — a dense full layer of 4
    heads, three window layers of 6 and a full one of 4 over 2
    key/value heads, each gated a head, the full ones rotating half a
    head, 2 of 8 (and 8 of 8) experts held beside the shared one, the
    picked gates times 2.5, the sliced vocabulary, the balance loss —
    loss and every leaf's gradient, the gates' and the routers'
    among them."""
    s = request.getfixturevalue(which)
    want_loss, want_grads, counts = s["want"]
    assert abs(s["loss"] - want_loss) <= LOSS_RTOL * want_loss
    gap, leaf = worst_gradient_gap(s["grads"], want_grads, s["before"])
    assert gap <= GRAD_TOL, (leaf, gap)
    for lp in s["grads"]["layers"]:
        assert np.asarray(lp["w_attn_gate"]).any()
    np.testing.assert_allclose(
        s["routing"][:, :-1] * s["picks"], counts, atol=1e-3)
    assert not s["routing"][:, -1].any()      # dropless
    # a gate a layer call, each the mean of a sigmoid
    assert s["gate"].shape == (5,)
    assert ((0.2 < s["gate"]) & (s["gate"] < 0.8)).all()


def test_the_gate_counter_is_the_references_mean_gate(stepped):
    x, _ = stepped["batch"]
    p, kw = stepped["before"], stepped["kw"]
    with jax.default_matmul_precision("highest"):
        h = jnp.asarray(p["embed"])[jnp.asarray(x)]
        a = ref._rmsnorm(h, p["layers"][0]["attn_norm"], kw["eps"])
        want = jnp.mean(jax.nn.sigmoid(a @ p["layers"][0]["w_attn_gate"]))
    np.testing.assert_allclose(stepped["gate"][0], want, rtol=1e-5)


# -- a wrong mechanism fails -----------------------------------------------------


def _tables(**kinds):
    tables = dict(CONFIG["rehearsal"]["rope_parameters"])
    for kind, over in kinds.items():
        tables[kind] = dict(tables[kind], **over)
    return tables


_REHEARSAL_TABLES = CONFIG["rehearsal"]["rope_parameters"]
WRONG = {
    "no_gate": dict(attention_gate=None),
    "full_layers_rotate_whole_heads": dict(rope_parameters=_tables(
        full_attention=dict(partial_rotary_factor=1))),
    "tables_swapped": dict(rope_parameters=dict(
        full_attention=_REHEARSAL_TABLES["sliding_attention"],
        sliding_attention=_REHEARSAL_TABLES["full_attention"])),
    "no_route_scale": dict(moe_route_scale=1.0),
    "no_shared_expert": dict(moe_shared_experts=0),
    "no_attention_factor": dict(rope_parameters=_tables(
        full_attention=dict(attention_factor=1.0))),
    "window_one_short": dict(sliding_window=7),
}


@pytest.mark.parametrize("wrong", WRONG, ids=str)
def test_a_wrong_mechanism_fails(stepped, wrong):
    """The tolerances are tight enough: each of these builds, on the
    same weights and batch, moves the loss off the reference's by
    more than a hundred ``LOSS_RTOL`` or a leaf's gradient by more
    than a hundred ``GRAD_TOL``.  (A layer's head count and whether
    its FFN is dense are read off its leaves: weights of other heads
    ARE another model, which the reference refuses, below.)"""
    knobs, _ = rehearsal(**WRONG[wrong])
    model = build(knobs)
    loss, grads, *_ = one_step(model, stepped["batch"], stepped["before"])
    want_loss, want_grads, _ = stepped["want"]
    loss_gap = abs(loss - want_loss) / want_loss
    grad_gap, _ = worst_gradient_gap(grads, want_grads, stepped["before"])
    assert loss_gap > 100 * LOSS_RTOL or grad_gap > 100 * GRAD_TOL, (
        loss_gap, grad_gap)


def test_the_reference_refuses_weights_of_another_head_count(stepped):
    kw = dict(stepped["kw"], heads_per_layer=[6, 4, 4, 4, 6])
    with pytest.raises(ValueError, match="published head_dim 16"):
        reference_step(stepped["before"], stepped["batch"], kw)


# -- the rotary tables ---------------------------------------------------------


def test_partial_yarn_table_at_the_published_numbers():
    """The full layers' table: 32 pairs (64 of a head's 128 channels)
    at theta 500000, YaRN's ramp computed at r = 64 — lo 9, hi 18 —
    the pairs under lo keep their frequency, those from hi on turn 128
    times slower; cos and sin times 1.4852030263919618 = 0.1 ln 128 +
    1.  The window layers' is the plain table of all 64 pairs at theta
    10000.  The reference writes both out by itself."""
    spec = PUBLISHED["rope_parameters"]["full_attention"]
    inv, factor = rope_table(spec, 128)
    assert inv.shape == (32,)
    i = np.arange(32)
    f = 500000.0 ** (-2 * i / 64)

    def d(n):
        return 64 * math.log(8192 / (2 * math.pi * n)) / (
            2 * math.log(500000))

    lo, hi = math.floor(d(32)), math.ceil(d(1))
    assert (lo, hi) == (9, 18)
    r = np.clip((i - lo) / (hi - lo), 0, 1)
    np.testing.assert_allclose(inv, f / 128 * r + f * (1 - r), rtol=1e-6)
    np.testing.assert_allclose(inv[:10], f[:10], rtol=1e-6)
    np.testing.assert_allclose(inv[18:], f[18:] / 128, rtol=1e-6)
    assert factor == spec["attention_factor"]
    assert abs(factor - (0.1 * math.log(128) + 1)) < 1e-12
    ref_inv, ref_factor = ref.rotary_table(spec, 128)
    np.testing.assert_allclose(inv, ref_inv, rtol=1e-6)
    assert ref_factor == factor
    window = PUBLISHED["rope_parameters"]["sliding_attention"]
    inv_w, factor_w = rope_table(window, 128)
    assert inv_w.shape == (64,) and factor_w == 1.0
    np.testing.assert_allclose(
        inv_w, 10000.0 ** (-2 * np.arange(64) / 128), rtol=1e-6)
    np.testing.assert_allclose(inv_w, ref.rotary_table(window, 128)[0])
    # an entry without the key is a whole head, as before
    whole = {k: v for k, v in window.items() if k != "partial_rotary_factor"}
    np.testing.assert_array_equal(rope_table(whole, 128)[0], inv_w)


def test_rope_at_a_partial_factor_passes_the_other_channels(rng):
    """``rope`` with the partial table rotates the LAST r channels of
    a head as the reference's ``_rope`` does and hands the first ``hd
    - r`` on bit for bit; a query times a key still depends on their
    distance alone."""
    spec = dict(PUBLISHED["rope_parameters"]["full_attention"],
                original_max_position_embeddings=16)
    hd, t = 16, 24
    inv, factor = rope_table(spec, hd)
    nope = hd - 2 * len(inv)
    assert nope == 8
    x = jnp.asarray(rng.standard_normal((1, 3, t, hd)), jnp.float32)
    pos = jnp.arange(t)
    got = rope(x, pos, 0.0, inv, factor, nope)
    np.testing.assert_array_equal(got[..., :nope], x[..., :nope])
    want = ref._rope(x[0].transpose(1, 0, 2), pos, *ref.rotary_table(spec, hd))
    np.testing.assert_allclose(got[0].transpose(1, 0, 2), want,
                               rtol=1e-5, atol=1e-5)
    shifted = rope(x, pos + 5, 0.0, inv, factor, nope)
    np.testing.assert_allclose(
        jnp.einsum("bhtd,bhsd->bhts", got, got),
        jnp.einsum("bhtd,bhsd->bhts", shifted, shifted),
        rtol=1e-4, atol=1e-4)


# -- the router ----------------------------------------------------------------


def test_the_softmax_router_honours_the_scale(rng):
    """The picked gates of the softmax branch, renormalised, times the
    routed scaling factor — the sum of a token's gates is the factor —
    and at 1.0 exactly what the branch gave before it took one."""
    x = jnp.asarray(rng.standard_normal((40, 16)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((16, 64)), jnp.float32)
    plain = moe.router_topk(x, w, 10, True)
    scaled = moe.router_topk(x, w, 10, True, scale=2.5)
    np.testing.assert_allclose(scaled[0], 2.5 * plain[0], rtol=1e-6)
    np.testing.assert_allclose(jnp.sum(scaled[0], -1), 2.5, rtol=1e-6)
    for a, b in zip(scaled[1:], plain[1:]):     # picks, scores, logits
        np.testing.assert_array_equal(a, b)
    one = moe.router_topk(x, w, 10, True, scale=1.0)
    np.testing.assert_array_equal(one[0], plain[0])
    unnormalised = moe.router_topk(x, w, 10, False, scale=2.5)
    np.testing.assert_allclose(
        unnormalised[0], 2.5 * moe.router_topk(x, w, 10, False)[0], rtol=1e-6)
    gate_matrix, idx, _ = ref.route(x, w, 10, 2.5)
    np.testing.assert_array_equal(np.sort(idx, -1), np.sort(scaled[1], -1))
    np.testing.assert_allclose(
        jnp.take_along_axis(gate_matrix, scaled[1], -1), scaled[0], rtol=1e-6)


def test_the_shares_add_up(rng):
    """E = 8 routed experts in 4 shares of 2 beside a shared expert:
    the routed parts the four shares give, plus what every rank
    computes alike — the residual and the shared expert — counted
    ONCE, equal what the uncut reference gives for the whole layer;
    so does the dense layer's path, which no share cuts.  Share r is
    asked for by rolling the router's columns so that its experts come
    first (``tests/test_mellum_moe.py``)."""
    n, d, f, e, k, scale = 48, 16, 8, 8, 3, 2.5
    x = jnp.asarray(rng.standard_normal((1, n, d)), jnp.float32)

    def w(*shape):
        return jnp.asarray(rng.standard_normal(shape) / 4, jnp.float32)

    lp = {
        "mlp_norm": jnp.ones((d,)), "router": 4 * w(d, e),
        "we_gate": w(e, d, f), "we_up": w(e, d, f), "we_down": w(e, f, d),
        "ws_gate": w(d, f), "ws_up": w(d, f), "ws_down": w(f, d),
    }
    with jax.default_matmul_precision("highest"):
        whole, counts, _ = ref.ffn(
            x[0], lp, top_k=k, route_scale=scale, eps=1e-6)
    m = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6)

    def share(r):
        lo = 2 * r
        y, aux = moe.moe_ffn(
            m, jnp.roll(lp["router"], -lo, axis=1),
            *(lp[name][lo:lo + 2] for name in ("we_gate", "we_up", "we_down")),
            n_experts=e, top_k=k, capacity_factor=None, expert_axis=None,
            model_axis=None, renormalize=True, route_scale=scale, held=2)
        return y[0], jnp.roll(aux["f"], lo), aux["lb"]

    parts, fs, lbs = zip(*(share(r) for r in range(4)))
    for f_share in fs:          # every share counts the picks of all 8
        np.testing.assert_allclose(f_share * n * k, counts, atol=1e-4)
    np.testing.assert_allclose(lbs, lbs[0], rtol=1e-6)
    shared = moe.shared_expert(
        m, lp["ws_gate"], lp["ws_up"], lp["ws_down"], None)[0]
    np.testing.assert_allclose(
        x[0] + sum(parts) + shared, x[0] + whole, rtol=2e-5, atol=2e-6)
    # the routed parts alone fall short by exactly the shared expert
    assert np.abs(np.asarray(sum(parts) - whole)).max() > 1e-3


# -- the band at a window as wide as the row block -----------------------------


def test_the_cells_window_is_the_kernels_row_block(monkeypatch):
    """At the cell's shape (T 8192, hd 128, bf16, window 512) the band
    kernels' resident block, walked block and score tile are all 512
    wide: a row block's band is two walked blocks — its own and the
    one before — and BOTH are crossed (the diagonal runs through one,
    the band's lower edge through the other), so every one of the 31
    tiles a head takes the masked body."""
    plan = attention._flash_tiles(8192, 8192, 128, jnp.bfloat16, 512)
    for tiles in plan:
        assert tuple(tiles) == (512, 512, 512)
    assert attention._band_steps(8192, 8192, plan.fwd, True, 512) == 2
    assert attention._band_steps(8192, 8192, plan.bwd, False, 512) == 2
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    cell = Llama(dict(program_knobs(CONFIG), n_train=2, n_val=1))
    tiles = cell.flash_tiles()
    for kernel in ("fwd", "bwd"):
        assert tiles["sliding_attention"][kernel] == {
            "outer": [512, 512], "inner": [512, 512], "tiles": 31,
            "masked_share": 1.0}
    assert set(tiles["full_attention"]) == {"fwd", "bwd"}
    assert tiles["full_attention"]["fwd"]["tiles"] == 136


@pytest.mark.parametrize("t,window,blocks", [
    (64, 16, 16),           # the cell's relation at a size the suite runs
    (1536, 512, None),      # the cell's own tiles, three row blocks
], ids=["w16_rows16", "w512_rows512"])
def test_band_kernels_at_window_equal_to_the_row_block(rng, t, window, blocks):
    """Forward and the three gradients of the band kernels in the
    interpreter, window = row block = walked block, 9 query heads to a
    key/value head's repeat, against dense attention with the explicit
    mask."""
    hd = 128 if blocks is None else 16
    h = 1 if blocks is None else 9
    q, k, v, g = (jnp.asarray(rng.standard_normal((1, h, t, hd)), jnp.float32)
                  for _ in range(4))
    if blocks is None:
        plan = attention._flash_tiles(t, t, hd, q.dtype, window)
        assert tuple(plan.fwd) == (512, 512, 512)
        kw = {}
    else:
        kw = dict(block_q=blocks, block_k=blocks)

    def kernels(q, k, v):
        return attention.flash_attention_tpu(
            q, k, v, causal=True, window=window, interpret=True, **kw)

    def dense(q, k, v):
        return attention.mha_reference(q, k, v, causal=True, window=window)

    out, vjp = jax.vjp(kernels, q, k, v)
    want, want_vjp = jax.vjp(dense, q, k, v)
    np.testing.assert_allclose(out, want, rtol=2e-5, atol=2e-5)
    for name, a, b in zip("qkv", vjp(g), want_vjp(g)):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4, err_msg=name)


# -- layouts ---------------------------------------------------------------------


def test_tp2_composes(stepped):
    """The heads of every layer (4 or 6: 2 or 3 a device), the gate's
    columns, the experts' and the dense layer's widths and the
    vocabulary over the ``model`` axis: loss, every leaf's gradient
    and the gate's counters equal one device's."""
    knobs, _ = rehearsal()
    model = build(knobs, model=2)
    loss, grads, routing, gate, _ = one_step(
        model, stepped["batch"], stepped["before"])
    assert abs(loss - stepped["loss"]) <= 2e-6 * stepped["loss"]
    gap, leaf = worst_gradient_gap(
        grads, stepped["grads"], stepped["before"])
    assert gap <= GRAD_TOL, (leaf, gap)
    np.testing.assert_array_equal(routing, stepped["routing"])
    np.testing.assert_allclose(gate, stepped["gate"], rtol=1e-6)


def test_dp2_composes(stepped):
    """Two replicas, a sequence each: the gate's counter is the mean
    over both."""
    knobs, _ = rehearsal(batch_size=1)
    model = build(knobs, data=2)
    loss, _, _, gate, _ = one_step(
        model, stepped["batch"], stepped["before"])
    assert abs(loss - stepped["loss"]) <= 2e-6 * stepped["loss"]
    np.testing.assert_allclose(gate, stepped["gate"], rtol=1e-5)


@pytest.mark.parametrize("layout", [dict(pp=5), dict(sp=2),
                                    dict(ut_steps=2)], ids=str)
@pytest.mark.parametrize("knob", [
    dict(attention_gate="per-head"), dict(n_heads_per_layer=[4] * 5)],
    ids=["gate", "heads"])
def test_layouts_it_does_not_compose_with_are_refused(knob, layout):
    with pytest.raises(NotImplementedError,
                       match="head count per layer, an attention gate"):
        Llama(dict(dim=64, n_layers=5, n_heads=4, n_kv_heads=2, seq_len=32,
                   batch_size=10, **knob, **layout))


def test_what_the_knobs_refuse():
    base = dict(dim=64, n_layers=2, n_heads=4, n_kv_heads=2)
    with pytest.raises(NotImplementedError, match="attention: mla"):
        Llama(dict(
            base, attention="mla", q_lora_rank=8, kv_lora_rank=8,
            qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=16,
            attention_gate="per-head"))
    with pytest.raises(ValueError, match="attention_gate"):
        Llama(dict(base, attention_gate="elementwise"))
    with pytest.raises(AssertionError, match="n_heads_per_layer"):
        Llama(dict(base, n_heads_per_layer=[4, 5]))     # 5 over 2 KV heads
    with pytest.raises(AssertionError, match="n_heads_per_layer"):
        Llama(dict(base, n_heads_per_layer=[4]))        # a layer unnamed
    with pytest.raises(AssertionError, match="n_kv_heads must divide by tp"):
        Llama(dict(base, n_heads_per_layer=[4, 6], tp=4))
    assert Llama(dict(base, n_heads_per_layer=[4, 6], tp=2)).tp == 2
    plain = Llama(base)
    assert plain.heads_per_layer == (4, 4) and not plain.attention_gate
    assert not plain.attn_per_layer
    assert plain.rotary_channels == {"full_attention": 16}


def test_a_gated_mtp_block_gives_its_counter_too():
    """The multi-token-prediction block is one more gated call: its
    counter rides out after the stack's."""
    knobs, _ = rehearsal(mtp_depth=1)
    model = build(knobs)
    loss, _, _, gate, _ = one_step(model, model.data.train_batch(0))
    assert np.isfinite(loss) and gate.shape == (6,)


# -- serving ---------------------------------------------------------------------


@pytest.mark.parametrize("knob", [
    dict(attention_gate="per-head"),
    dict(n_heads_per_layer=[2, 4]),
    dict(rope_parameters={"full_attention": {
        "rope_theta": 1e4, "partial_rotary_factor": 0.5}}),
], ids=["gate", "heads", "partial_rotary"])
def test_serving_is_refused(knob):
    model = build(dict(
        dim=32, n_layers=2, n_heads=2, vocab=32, seq_len=32, batch_size=2,
        n_train=8, n_val=4, compute_dtype="float32", **knob))
    for paged in (False, True):
        with pytest.raises(NotImplementedError, match="one head count"):
            model.make_decoder(paged=paged)
    doc = (ROOT / "docs/REFUSALS.md").read_text()
    assert "serving has one head count, no gate and whole-head rotation" in doc
    assert "## Declared refusals (25)" in doc


# -- what the remat keeps ----------------------------------------------------------


def test_kept_attention_calls_weigh_their_own_layers_heads():
    """``ATTN_RESIDUALS`` of a call at THAT layer's heads: a window
    layer's call keeps q of 72 heads where a full layer's keeps 48;
    the rule takes the last calls first, each at its own weight, while
    they fit beside the step's estimate."""
    from theanompi_tpu.models import llama

    model = Llama(dict(program_knobs(CONFIG), n_train=2, n_val=1))
    n_tok, row = 8192, 3072
    full = n_tok * ((48 + 16) * 128 + row) * 2
    band = n_tok * ((72 + 16) * 128 + row) * 2
    assert [model.remat_kept_attn_bytes_of(i) for i in range(5)] == [
        full, band, band, band, full]
    assert model.remat_kept_attn_bytes_per_call == band
    mlp = model.remat_kept_bytes_per_call
    assert mlp == 2 * n_tok * 12288 * 2
    # the estimate counts the flash kernel's outputs at each layer's
    # heads: 2 x 48 + 3 x 72 = 312 heads' rows and logsumexps
    params = 811_017_216
    assert model._local_params({"model": 1, "pipe": 1, "expert": 1})[0] == params
    assert model.step_peak_estimate() == (
        16 * params + 5 * n_tok * row * 2
        + 312 * (n_tok * 128 * 2 + n_tok * 4) + 2 * n_tok * 12544 * 2)
    room = llama.REMAT_RESERVE_BYTES + model.step_peak_estimate()
    # the dense layer's products first, then the last full layer's
    # call, then the window layers' before it
    # ... and the expert calls' rows and products from what those
    # leave: 5120 rows (twice 8 of 256 experts' share of 81920 picks)
    # of 3072 and twice 1024, and the sort's two int32 arrays
    moe = 5120 * (row + 2 * 1024) * 2 + 2 * 81920 * 4
    assert model.remat_kept_moe_bytes_per_call == moe
    attn = 2 * full + 3 * band
    for extra, want in [
        (0, (0, 0, 0)), (mlp, (1, 0, 0)), (mlp + full, (1, 1, 0)),
        (mlp + full + band - 1, (1, 1, 4)), (mlp + full + band, (1, 2, 0)),
        (mlp + attn, (1, 5, 0)), (mlp + attn + moe, (1, 5, 1)),
        (mlp + attn + 4 * moe - 1, (1, 5, 3)),
        (mlp + attn + 5 * moe, (1, 5, 4)),
    ]:
        assert model.remat_keep_calls(room + extra) == want, extra
    model.remat_kept_calls, model.remat_kept_attn_calls = 1, 2
    model.remat_kept_moe_calls = 3
    assert model.remat_kept_bytes == mlp + full + band + 3 * moe


# -- the file ------------------------------------------------------------------


def test_the_file_is_the_published_config_cut_three_ways():
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if catalog.exists():
        row = next(json.loads(line) for line in catalog.read_text().splitlines()
                   if '"Laguna-S-2.1"' in line)
        assert PUBLISHED == row["config"]
        assert CONFIG["source"] == row["source_url"]
    assert sorted(CONFIG["reduced"]) == [
        "num_experts", "num_hidden_layers", "vocab_size"]
    changed = {k for k, v in PUBLISHED.items() if CONFIG[k] != v}
    assert changed == set(CONFIG["reduced"])
    assert (CONFIG["num_hidden_layers"], CONFIG["num_experts"],
            CONFIG["vocab_size"]) == (5, 8, 12544)
    for key in ("assumed", "deployment", "learns", "kernels", "rehearsal"):
        assert CONFIG[key], key
    knobs = program_knobs(CONFIG)
    model = Llama(dict(knobs, n_train=2, n_val=1))
    assert model.heads_per_layer == (48, 72, 72, 72, 48)
    assert model.rotary_channels == {
        "full_attention": 64, "sliding_attention": 128}
    assert (model.n_experts, model.moe_experts_held, model.moe_top_k) == (
        256, 8, 10)
    assert model.moe_route_scale == 2.5 and model.dense_ffn_dim == 12288
    assert moe.held_rows_bound(81920, 8, 256) == 5120


def test_the_files_flops_are_the_models_sum():
    """The dense count's kwargs reproduce this model's own sum, written
    out from its shapes."""
    d, hd, t, v = 3072, 128, 8192, 12544
    attn = {h: 2 * d * h * hd + 2 * d * 8 * hd + d * h for h in (48, 72)}
    expert = 3 * d * 1024
    params = (2 * attn[48] + 3 * attn[72] + 3 * d * 12288 + 4 * expert
              + 4 * 10 * 8 / 256 * expert + d * v)
    assert params == 479_109_120
    products = 2 * (2 * t * 48 * hd) + 3 * (4 * (512 - 512 ** 2 / (2 * t))
                                            * 72 * hd)
    assert products == 256_180_224
    spec = CONFIG["flops_per_item"]
    assert getattr(flops, spec["fn"])(**spec["kwargs"]) == 3 * (
        2 * params + products) == 3_643_195_392


# -- the summary ---------------------------------------------------------------


def test_summary_names_the_mechanisms():
    from theanompi_tpu import BSP
    from theanompi_tpu.obs import last_gate_counters

    knobs, _ = rehearsal(optimizer="adam", lr=1e-3, n_epochs=1,
                         device_data_cache=True, steps_per_call=2)
    rule = BSP()
    rule.init(devices=[0], modelfile="theanompi_tpu.models.llama",
              modelclass="Llama", launch="inprocess", config=knobs,
              verbose=False)
    res = rule.wait()
    assert res["heads_per_layer"] == (4, 6, 6, 6, 4)
    assert res["attention_gate"] is True
    assert res["rotary_channels"] == {
        "full_attention": 8, "sliding_attention": 16}
    assert res["attention_kinds"] == {
        "full_attention": 2, "sliding_attention": 3}
    assert res["experts_held"] == 2
    gates = res["attn_gate_counters"]["attn_gate_open"]
    assert len(gates) == 5 and all(0.2 < g < 0.8 for g in gates)
    assert last_gate_counters() == res["attn_gate_counters"]
    assert len(res["moe_counters"]["moe_rows_held"]) == 4
