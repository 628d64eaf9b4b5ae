"""The ``mellum`` mechanisms of ``models/llama.py`` and
``ops/attention.py`` — a head dim that is a configuration value, an
attention kind per layer (window and full layers mixed), a rotary
table per kind with YaRN on the full ones, beside the held range of
softmax-routed experts with renormalised picks and the balance loss —
against the plain reference ``benchmark/reference/mellum_moe.py`` on
seeded weights, small widths, float32, on the CPU.

Tolerances: program and reference are both float32 here and differ in
the order of their sums (the program gathers sorted rows into grouped
products, the reference multiplies a dense gate matrix; the dense
attention path against a per-head softmax), so losses agree to 1e-6
relative and gradients to 1e-4 of a leaf's largest entry (the worst
leaf read 2e-5 when this was written).  A window off by one, a
missing YaRN table or factor, the wrong table on the window layers, a
shifted pattern or gates not renormalised move the loss by 1e-4 to
1e-2 of itself; a missing balance loss leaves the loss 1e-3 short and
the routers without any gradient (``test_a_wrong_mechanism_fails``).
"""

import json
import math
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import mellum_moe as ref
from benchmark.run import program_knobs
from theanompi_tpu.models.llama import Llama, rope, rope_at, rope_table
from theanompi_tpu.parallel import make_mesh, moe
from theanompi_tpu.utils import Recorder

LOSS_RTOL = 1e-6
GRAD_TOL = 1e-4

CONFIG = json.loads(
    (Path(__file__).parents[1] / "benchmark" / "configs"
     / "mellum2_12b_a2.5b_train_ep4_l4.json").read_text()
)
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


def one_step(model, batch=None, lr=1.0):
    """One step (SGD at lr 1) on ``batch`` (default: the first):
    ``(loss, the parameters' change = the gradient, routing counters,
    the parameters before)``."""
    before = jax.device_get(model.params)
    x, y = model.put_batch(batch or model.data.train_batch(0))
    out = model._train_step(
        model.params, model.opt_state, model.ef_state, x, y,
        jnp.float32(lr))
    model.params, model.opt_state, model.ef_state = out[:3]
    loss, _, routing = out[3:]
    grads = jax.tree.map(
        lambda a, b: np.asarray(a) - np.asarray(b), before,
        jax.device_get(model.params))
    return float(loss), grads, np.asarray(routing), before


def worst_gradient_gap(got, want, before) -> tuple[float, str]:
    """The largest difference of a leaf over that leaf's largest
    entry of ``want``, and the leaf.  ``got`` is a difference of
    float32 parameters (``one_step``), exact to half an ulp of the
    parameter: that much of the difference is the subtraction's, not
    the gradient's (it shows on the routers, whose gradient is 0.001
    times the balance loss's, 1e-4 of their weights)."""
    flat_got = jax.tree_util.tree_flatten_with_path(got)[0]
    flat_want = jax.tree.leaves(want)
    assert len(flat_got) == len(flat_want)
    worst = (0.0, "")
    for (path, g), w, p in zip(flat_got, flat_want, jax.tree.leaves(before)):
        w = np.asarray(w)
        scale = np.abs(w).max()
        assert scale > 0, jax.tree_util.keystr(path)
        ulp = np.finfo(np.float32).eps * np.abs(p).max()
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


@pytest.fixture(scope="module")
def stepped():
    knobs, kw = rehearsal()
    model = build(knobs)
    batch = model.data.train_batch(0)
    loss, grads, routing, before = one_step(model, batch)
    want = reference_step(before, batch, kw)
    return dict(model=model, batch=batch, kw=kw, loss=loss, grads=grads,
                routing=routing, before=before, want=want,
                picks=batch[0].size * knobs["moe_top_k"])


def test_the_rehearsal_has_both_kinds_a_binding_window_and_wide_heads():
    knobs, kw = rehearsal()
    model = Llama(knobs)
    assert model.attn_kinds == ("sliding_attention",) * 3 + (
        "full_attention",)
    assert model.sliding_window < model.seq_len
    assert model.n_heads * model.head_dim != model.dim
    assert model.moe_experts_held < model.n_experts
    assert kw["layer_types"] == list(model.attn_kinds)


def test_step_loss_and_every_gradient_match_the_reference(stepped):
    """The rehearsal preset's whole step — three window layers and a
    full one, each with 2 of 8 experts held, heads of 16 over a width
    of 64, the sliced vocabulary, the balance loss — loss and every
    leaf's gradient, the routers' (the balance loss's alone) among
    them."""
    want_loss, want_grads, counts = stepped["want"]
    assert abs(stepped["loss"] - want_loss) <= LOSS_RTOL * want_loss
    gap, leaf = worst_gradient_gap(
        stepped["grads"], want_grads, stepped["before"])
    assert gap <= GRAD_TOL, (leaf, gap)
    np.testing.assert_allclose(
        stepped["routing"][:, :-1] * stepped["picks"], counts, atol=1e-3)
    assert not stepped["routing"][:, -1].any()      # dropless


def test_the_routers_gradient_is_the_balance_losss_alone(stepped):
    """A share by itself cuts the gates' gradient; what the routers
    get is ``moe_aux_coef`` times the balance loss's, and without the
    loss exactly nothing."""
    knobs, kw = rehearsal(moe_aux_coef=0.0)
    model = build(knobs)
    model.params = jax.device_put(
        stepped["before"], model._shardings(model._specs))
    _, grads, _, _ = one_step(model, stepped["batch"])
    for got, with_loss in zip(grads["layers"], stepped["grads"]["layers"]):
        assert not np.asarray(got["router"]).any()
        assert np.asarray(with_loss["router"]).any()


# -- the rotary tables ---------------------------------------------------------


def test_yarn_table_at_the_published_numbers():
    """The full layers' table against the formulas in numpy: 64 pairs
    at theta 500000; the pairs under ``lo`` 18 keep their frequency,
    those from ``hi`` 35 on turn 16 times slower, a linear blend
    between; cos and sin times 1.2772588722239782 = 0.1 ln 16 + 1."""
    spec = PUBLISHED["rope_parameters"]["full_attention"]
    hd = PUBLISHED["head_dim"]
    inv, factor = rope_table(spec, hd)
    i = np.arange(64)
    f = 500000.0 ** (-2 * i / 128)

    def d(n):
        return 128 * math.log(8192 / (2 * math.pi * n)) / (
            2 * math.log(500000))

    lo, hi = math.floor(d(32)), math.ceil(d(1))
    assert (lo, hi) == (18, 35)
    r = np.clip((i - lo) / (hi - lo), 0, 1)
    np.testing.assert_allclose(inv, f / 16 * r + f * (1 - r), rtol=1e-6)
    np.testing.assert_allclose(inv[:19], f[:19], rtol=1e-6)
    np.testing.assert_allclose(inv[35:], f[35:] / 16, rtol=1e-6)
    assert factor == spec["attention_factor"]
    assert abs(factor - (0.1 * math.log(16) + 1)) < 1e-12
    # the factor is computed where none is published
    no_factor = {k: v for k, v in spec.items() if k != "attention_factor"}
    assert abs(rope_table(no_factor, hd)[1] - factor) < 1e-12
    # the reference writes the same table out by itself
    ref_inv, ref_factor = ref.rotary_table(spec, hd)
    np.testing.assert_allclose(inv, ref_inv, rtol=1e-6)
    assert ref_factor == factor


def test_a_window_layers_table_is_plain_rope(rng):
    spec = PUBLISHED["rope_parameters"]["sliding_attention"]
    inv, factor = rope_table(spec, 128)
    assert factor == 1.0
    x = jnp.asarray(rng.standard_normal((1, 2, 40, 128)), jnp.float32)
    pos = jnp.arange(40)
    np.testing.assert_allclose(
        rope(x, pos, 0.0, inv, factor), rope(x, pos, 500000.0),
        # (the table's powers are float64's rounded, ``rope``'s
        # float32's: 1e-7 of an angle of up to 40)
        rtol=1e-5, atol=2e-5)
    # and the per-row form is the same rotation
    rows = x[0, :, 7, :][None]
    np.testing.assert_allclose(
        rope_at(rows, jnp.array([7]), 500000.0)[0],
        rope(x, pos, 500000.0)[0, :, 7, :], rtol=1e-6, atol=1e-6)
    with pytest.raises(NotImplementedError, match="rope_type"):
        rope_table({"rope_type": "llama3", "rope_theta": 1e4}, 128)


def test_the_factor_scales_cos_and_sin(rng):
    x = jnp.asarray(rng.standard_normal((1, 1, 8, 16)), jnp.float32)
    pos = jnp.arange(8)
    inv = rope_table({"rope_theta": 100.0}, 16)[0]
    np.testing.assert_allclose(
        rope(x, pos, 0.0, inv, 1.25), 1.25 * rope(x, pos, 100.0),
        rtol=1e-6, atol=1e-6)


# -- a wrong mechanism fails -----------------------------------------------------


def _tables(kind, **over):
    tables = dict(CONFIG["rehearsal"]["rope_parameters"])
    tables[kind] = dict(tables[kind], **over)
    return tables


WRONG = {
    "window_one_short": dict(sliding_window=7),
    "window_one_long": dict(sliding_window=9),
    "no_yarn": dict(
        rope_parameters=_tables("full_attention", rope_type="default")),
    "no_factor": dict(
        rope_parameters=_tables("full_attention", attention_factor=1.0)),
    "full_table_on_window": dict(rope_parameters=dict(
        CONFIG["rehearsal"]["rope_parameters"],
        sliding_attention=CONFIG["rehearsal"]["rope_parameters"][
            "full_attention"])),
    "pattern_shifted": dict(layer_types=PUBLISHED["layer_types"][1:]),
    "not_renormalised": dict(moe_renormalize=False),
    "no_balance_loss": dict(moe_aux_coef=0.0),
}


@pytest.mark.parametrize("wrong", WRONG, ids=str)
def test_a_wrong_mechanism_fails(stepped, wrong):
    """The tolerances are tight enough: each of these builds, on the
    same weights and batch, moves the loss off the reference's by more
    than a hundred ``LOSS_RTOL`` or a leaf's gradient by more than a
    hundred ``GRAD_TOL``."""
    knobs, _ = rehearsal(**WRONG[wrong])
    model = build(knobs)
    model.params = jax.device_put(
        stepped["before"], model._shardings(model._specs))
    loss, grads, _, _ = one_step(model, stepped["batch"])
    want_loss, want_grads, _ = stepped["want"]
    loss_gap = abs(loss - want_loss) / want_loss
    grad_gap, _ = worst_gradient_gap(grads, want_grads, stepped["before"])
    assert loss_gap > 100 * LOSS_RTOL or grad_gap > 100 * GRAD_TOL, (
        loss_gap, grad_gap)


def test_heads_of_dim_over_n_heads_are_not_the_published_heads(stepped):
    """``head_dim`` left out is ``dim // n_heads`` (8 of the
    rehearsal's 64 / 8, 72 of the published 2304 / 32) where the
    published heads are 16 (128): other leaves, which the published
    shapes refuse, and on the right weights cut to them another
    loss."""
    knobs, _ = rehearsal()
    right = stepped["before"]["layers"][0]
    assert right["wq"].shape == (64, 8 * 16) and right["wo"].shape == (128, 64)
    del knobs["head_dim"]
    model = build(knobs)
    wrong = jax.device_get(model.params)["layers"][0]
    assert wrong["wq"].shape == (64, 64) and wrong["wk"].shape == (64, 16)

    def cut(w, heads, axis):
        """The first 8 of each head's 16 channels."""
        shape = list(w.shape)
        shape[axis:axis + 1] = [heads, 16]
        w = np.take(w.reshape(shape), np.arange(8), axis=axis + 1)
        shape[axis:axis + 2] = [heads * 8]
        return w.reshape(shape)

    params = dict(stepped["before"], layers=[
        dict(lp, wq=cut(lp["wq"], 8, 1), wk=cut(lp["wk"], 2, 1),
             wv=cut(lp["wv"], 2, 1), wo=cut(lp["wo"], 8, 0))
        for lp in stepped["before"]["layers"]
    ])
    model.params = jax.device_put(params, model._shardings(model._specs))
    loss, *_ = one_step(model, stepped["batch"])
    assert abs(loss - stepped["loss"]) > 100 * LOSS_RTOL * stepped["loss"]
    # the reference is told the published head dim and refuses them
    with pytest.raises(ValueError, match="published head_dim 16"):
        reference_step(params, stepped["batch"], stepped["kw"])
    with pytest.raises(AssertionError):
        Llama(dict(dim=60, n_heads=8))          # 60 / 8: no such heads
    assert Llama(dict(dim=60, n_heads=8, head_dim=16)).head_dim == 16


def test_softmax_then_renormalise_is_a_softmax_over_the_picked(rng):
    """A softmax over all 64 scores, the 8 largest, divided by their
    sum — the published order, the program's and the reference's — is
    a softmax over the 8 picked scores alone: nobody needs to turn one
    into the other."""
    x = jnp.asarray(rng.standard_normal((40, 16)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((16, 64)), jnp.float32)
    gates, eidx, probs, logits = moe.router_topk(x, w, 8, True)
    picked = jnp.take_along_axis(logits, eidx, axis=-1)
    np.testing.assert_allclose(
        gates, jax.nn.softmax(picked, axis=-1), rtol=2e-5, atol=1e-7)
    np.testing.assert_allclose(jnp.sum(gates, -1), 1.0, rtol=1e-6)
    gate_matrix, idx, _ = ref.route(x, w, 8)
    np.testing.assert_array_equal(np.sort(idx, -1), np.sort(eidx, -1))
    np.testing.assert_allclose(
        jnp.take_along_axis(gate_matrix, eidx, -1), gates, rtol=1e-6)


def test_the_shares_add_up(rng):
    """E = 8 routed experts in 4 shares of 2: the parts the four
    shares give, plus the residual (with the attention branch in it)
    counted ONCE, equal what the uncut reference gives for the whole
    layer.  A share holds experts ``[0, held)``; share r is asked for
    by rolling the router's columns so that its experts come first —
    the same scores, the same picks, the same gates under other
    names."""
    n, d, f, e, k = 48, 16, 8, 8, 3
    x = jnp.asarray(rng.standard_normal((1, n, d)), jnp.float32)
    lp = {
        "mlp_norm": jnp.ones((d,)),
        "router": jnp.asarray(rng.standard_normal((d, e)), jnp.float32),
        "we_gate": jnp.asarray(rng.standard_normal((e, d, f)) / 4, jnp.float32),
        "we_up": jnp.asarray(rng.standard_normal((e, d, f)) / 4, jnp.float32),
        "we_down": jnp.asarray(rng.standard_normal((e, f, d)) / 4, jnp.float32),
    }
    with jax.default_matmul_precision("highest"):
        whole, counts, _ = ref.ffn(x[0], lp, top_k=k, eps=1e-6)
    m = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6)

    def share(r):
        lo = 2 * r
        y, aux = moe.moe_ffn(
            m, jnp.roll(lp["router"], -lo, axis=1),
            *(lp[name][lo:lo + 2] for name in ("we_gate", "we_up", "we_down")),
            n_experts=e, top_k=k, capacity_factor=None, expert_axis=None,
            model_axis=None, renormalize=True, held=2)
        return y[0], jnp.roll(aux["f"], lo), aux["lb"]

    parts, fs, lbs = zip(*(share(r) for r in range(4)))
    for f_share in fs:          # every share counts the picks of all 8
        np.testing.assert_allclose(f_share * n * k, counts, atol=1e-4)
    # the balance loss is whole on every share
    np.testing.assert_allclose(lbs, lbs[0], rtol=1e-6)
    np.testing.assert_allclose(
        x[0] + sum(parts), x[0] + whole, rtol=2e-5, atol=2e-6)


# -- the attention kinds ---------------------------------------------------------


def test_attention_of_each_kind_matches_the_reference(stepped):
    """One layer's attention branch of each kind through the program's
    ``_gqa`` against the reference's ``attention`` with its explicit
    mask and table: the window layer differs from the full layer, and
    each equals its own."""
    from jax.sharding import PartitionSpec as P

    from theanompi_tpu.models.llama import rms_norm
    from theanompi_tpu.parallel import tp as tp_lib

    model, kw = stepped["model"], stepped["kw"]
    lp = stepped["before"]["layers"][0]
    x = jnp.asarray(
        np.random.default_rng(5).standard_normal((1, 32, 64)), jnp.float32)

    def program(kind):
        def fn(p, x):
            xn = rms_norm(x, p["attn_norm"], model.norm_eps)
            o = model._gqa(p, xn, jnp.arange(x.shape[1]), kind)
            b, h, t, d = o.shape
            return tp_lib.row_parallel(
                o.transpose(0, 2, 1, 3).reshape(b, t, h * d), p["wo"])

        specs = model._layer_specs("moe")
        return jax.jit(jax.shard_map(
            fn, mesh=model.mesh, in_specs=(specs, P()), out_specs=P(),
        ))(lp, x)[0]

    got = {kind: program(kind) for kind in set(model.attn_kinds)}
    assert np.abs(got["full_attention"] - got["sliding_attention"]).max() > 1e-3
    for kind, out in got.items():
        with jax.default_matmul_precision("highest"):
            want = ref.attention(
                x[0], lp, kind, n_heads=kw["n_heads"],
                n_kv_heads=kw["n_kv_heads"], head_dim=kw["head_dim"],
                sliding_window=kw["sliding_window"],
                rope_parameters=kw["rope_parameters"], eps=kw["eps"])
        np.testing.assert_allclose(out, want, rtol=2e-4, atol=2e-6,
                                   err_msg=kind)


def test_layer_types_are_the_published_list_cut_to_the_stack():
    """The published list's first ``n_layers`` entries (a stack cut in
    depth keeps its leading layers' kinds); an unknown kind, too short
    a list and a window layer without a window are refused."""
    base = dict(n_layers=4, sliding_window=8)
    model = Llama(dict(base, layer_types=PUBLISHED["layer_types"]))
    assert model.attn_kinds == tuple(PUBLISHED["layer_types"][:4])
    assert model.attention_kinds == {
        "full_attention": 1, "sliding_attention": 3}
    assert model.window_of("sliding_attention") == 8
    assert model.window_of("full_attention") is None
    with pytest.raises(ValueError, match="layer_types"):
        Llama(dict(base, layer_types=["full_attention"] * 3))
    with pytest.raises(ValueError, match="layer_types"):
        Llama(dict(base, layer_types=["chunked_attention"] * 4))
    with pytest.raises(ValueError, match="sliding_window"):
        Llama(dict(n_layers=4, layer_types=PUBLISHED["layer_types"]))
    plain = Llama(dict(n_layers=4))
    assert plain.attn_kinds == ("full_attention",) * 4
    assert not plain.attn_per_layer and plain.sliding_window is None


# -- a head dim of its own: trains, checkpoints, restores, splits ---------------


def test_wide_heads_train_checkpoint_and_restore(tmp_path):
    knobs, _ = rehearsal(optimizer="adam", lr=1e-3)
    model = build(knobs)
    first = one_step(model, lr=1e-3)[0]
    for _ in range(3):
        last = one_step(model, lr=1e-3)[0]
    assert last < first
    model.save(str(tmp_path), Recorder())
    want = jax.device_get(model.params)
    fresh = build(knobs)
    assert fresh.load(str(tmp_path))
    jax.tree.map(np.testing.assert_array_equal,
                 jax.device_get(fresh.params), want)
    assert abs(one_step(fresh, lr=1e-3)[0]
               - one_step(model, lr=1e-3)[0]) < 1e-6


def test_tp2_composes(stepped):
    """Heads of 16 over the ``model`` axis (4 query heads and one
    key/value head a device), the experts' width and the vocabulary
    sharded: loss and every leaf's gradient equal one device's, both
    attention kinds."""
    knobs, _ = rehearsal()
    model = build(knobs, model=2)
    loss, grads, routing, _ = one_step(model, stepped["batch"])
    assert abs(loss - stepped["loss"]) <= 2e-6 * stepped["loss"]
    gap, leaf = worst_gradient_gap(
        grads, stepped["grads"], stepped["before"])
    assert gap <= GRAD_TOL, (leaf, gap)
    np.testing.assert_array_equal(routing, stepped["routing"])


# -- what it does not compose with -------------------------------------------------


@pytest.mark.parametrize("layout", [dict(pp=2), dict(sp=2),
                                    dict(ut_steps=2)], ids=str)
def test_layouts_it_does_not_compose_with_are_refused(layout):
    knobs, _ = rehearsal(**layout)
    with pytest.raises(NotImplementedError,
                       match="layer_types.*does not yet compose"):
        Llama(knobs)


def test_latent_attention_with_a_window_is_refused():
    with pytest.raises(NotImplementedError, match="attention: mla"):
        Llama(dict(
            attention="mla", q_lora_rank=8, kv_lora_rank=8,
            qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=16,
            n_layers=2, layer_types=["sliding_attention"] * 2,
            sliding_window=8))


def test_serving_is_refused(stepped):
    plain = build(dict(
        dim=32, n_layers=2, n_heads=2, vocab=32, seq_len=32, batch_size=2,
        n_train=8, n_val=4, compute_dtype="float32",
        layer_types=["sliding_attention", "full_attention"],
        sliding_window=8))
    with pytest.raises(NotImplementedError, match="one cache lifetime"):
        plain.make_decoder()
    with pytest.raises(NotImplementedError, match="one cache lifetime"):
        plain.make_decoder(paged=True)


# -- the summary ---------------------------------------------------------------


def test_summary_names_the_mechanisms(monkeypatch):
    from theanompi_tpu import BSP
    from theanompi_tpu.ops import attention

    knobs, _ = rehearsal(optimizer="adam", lr=1e-3, n_epochs=1,
                         device_data_cache=True, steps_per_call=2)
    rule = BSP()
    rule.init(devices=[0], modelfile="theanompi_tpu.models.llama",
              modelclass="Llama", launch="inprocess", config=knobs,
              verbose=False)
    res = rule.wait()
    assert res["attention"] == "gqa"
    assert res["attention_kinds"] == {
        "full_attention": 1, "sliding_attention": 3}
    assert res["sliding_window"] == 8
    assert res["experts_held"] == 2
    # dense attention here: a summary a kind, each empty
    assert res["flash_tiles"] == {
        "full_attention": {}, "sliding_attention": {}}
    counters = res["moe_counters"]
    assert counters["moe_experts_held"] == 2
    assert len(counters["moe_rows_held"]) == 4
    # on the chip, at the cell's shape: the band's tiles beside the
    # triangle's
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    cell = Llama(dict(program_knobs(CONFIG), n_train=4, n_val=1))
    tiles = cell.flash_tiles()
    assert tiles["sliding_attention"]["fwd"]["tiles"] == 45
    assert tiles["full_attention"]["fwd"]["tiles"] == 136
    assert tiles["sliding_attention"]["fwd"]["outer"] == [512, 512]
    assert all(set(kind) == {"fwd", "bwd"} for kind in tiles.values())
    # and the plain models keep the flat summary
    assert set(Llama(dict(seq_len=4096, dim=1024)).flash_tiles()) == {
        "fwd", "bwd"}
