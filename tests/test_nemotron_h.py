"""The ``nemotron_h`` decoder (``Llama`` with ``layer_types`` a pattern: blocks
that are a Mamba-2 mixer, an attention or an expert layer ALONE;
two-product squared-ReLU experts in a latent under a sigmoid router
with a selection bias, beside a shared expert; a head share) against
its plain reference (``benchmark/reference/nemotron_h.py``) on the CPU
in float32, at the small sizes the benchmark's configuration keeps
under ``rehearsal``: loss, every leaf's gradient, the picks, the bias;
every wrong build of ``benchmark/tools/nemotron_check.py`` fails
there; the tie of the head and expert shares to the UNCUT block; a
mixer directly followed by attention; the knobs' refusals."""

from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import nemotron_h as ref
from benchmark.tools import glm_check, nemotron_check
from theanompi_tpu.models.llama import Llama
from theanompi_tpu.ops import ssd
from theanompi_tpu.parallel.moe import held_rows_bound

ROOT = Path(__file__).resolve().parents[1]
CELL = "nemotron3_super_train_t8192"
SEED = 11


@pytest.fixture(scope="module")
def held():
    """The right program and every wrong build, each held to the
    reference once (``nemotron_check.check`` at the rehearsal sizes)."""
    with jax.default_matmul_precision("highest"):
        return nemotron_check.check(
            CELL, SEED, list(nemotron_check.VARIANTS), rehearsal=True,
            control=True)


def test_program_equals_the_reference_in_float32(held):
    right = held["right"]
    assert held["ok"] and right["ok"]
    assert right["loss_rel"] < 1e-6
    assert right["grad_rel_worst"] < 1e-3, right["grad_rel_worst_leaf"]
    assert right["grad_rel_worst_routed"] < 1e-4
    # a share by itself holds its routers: exactly 0 on both sides
    assert right["grad_rel_router"] == 0
    assert right["count_rel_worst"] == 0
    # embed, final_norm, lm_head; 5 M blocks of 9 leaves, the * block
    # of 5, 5 E blocks of 8 (no gate leaf: two-product experts)
    assert len(right["grad_rel"]) == 3 + 5 * 9 + 5 + 5 * 8
    assert {"layers.0.ssm_in", "layers.7.wo", "layers.1.w_lat_down",
            "layers.1.w_lat_up", "layers.1.we_up", "layers.1.we_down",
            "layers.1.ws_up", "layers.1.router", "lm_head"} <= set(
        right["grad_rel"])
    assert not {"layers.1.we_gate", "layers.1.ws_gate", "layers.1.attn_norm",
                "layers.0.mlp_norm"} & set(right["grad_rel"])


def test_the_selection_bias_moves_as_state(held):
    """After the step every entry of ``net_state["moe_bias"]`` stands
    ``rate`` from where it started, toward balance, as the reference's
    rule moves it from the reference's own counts."""
    assert held["right"]["bias_moved_alike"] == 1.0
    assert len(held["right"]["rows_held"]) == 5


def test_scan_counters_say_the_carry_is_alive(held):
    right = held["right"]
    assert len(right["ssm_state_rms"]) == 5
    assert min(right["ssm_state_rms"]) > 0
    assert max(right["ssm_log_decay_min"]) < 0


@pytest.mark.parametrize("variant", sorted(nemotron_check.VARIANTS))
def test_a_wrong_build_fails_a_limit(held, variant):
    got = held["variants"][variant]
    assert not got["ok"], got
    assert held["failed"][variant]


def test_the_reference_in_a_lower_precision_fails_too(held):
    assert held["failed"][glm_check.CONTROL]


# -- the tie of the share to the model ---------------------------------------

T, D = 24, 32
RANKS = 4
HQ, HKV, HD = 8, 2, 8                   # attention, uncut
MH, MP, MN, MG = 8, 8, 8, 4             # the mixer, uncut
E, K, DL, F, FS = 16, 4, 16, 24, 40     # experts, uncut


def _normal(key, *shape):
    return 0.3 * jax.random.normal(jax.random.PRNGKey(key), shape)


@pytest.fixture(scope="module")
def a():
    return _normal(0, T, D)


def _cols(w, *slices):
    return jnp.concatenate([w[..., s] for s in slices], axis=-1)


def test_the_mixers_head_shares_add_up_to_the_uncut_block(a):
    """Over all 4 head shares (2 of 8 state heads and ONE of 4 B/C
    groups each, so the gated norm's groups lie on a rank each) the
    partial ``W_out`` products add up to the uncut mixer's result."""
    inner, gn = MH * MP, MG * MN
    full = {
        "ssm_in": _normal(1, D, 2 * inner + 2 * gn + MH),
        "ssm_conv_w": _normal(2, 4, inner + 2 * gn),
        "ssm_conv_b": _normal(3, inner + 2 * gn),
        "ssm_dt_bias": _normal(4, MH), "ssm_a_log": _normal(5, MH),
        "ssm_d": 1 + _normal(6, MH), "ssm_norm": 1 + _normal(7, inner),
        "ssm_out": _normal(8, inner, D),
    }
    kw = dict(mamba_d_head=MP, mamba_d_state=MN, eps=1e-5)
    with jax.default_matmul_precision("highest"):
        whole = ref.mamba(a, full, mamba_n_heads=MH, mamba_n_groups=MG, **kw)
        parts = []
        for r in range(RANKS):
            h = slice(r * MH // RANKS, (r + 1) * MH // RANKS)
            ch = slice(h.start * MP, h.stop * MP)
            g = slice(r * MN, (r + 1) * MN)     # the rank's one group

            def conv(w):
                return _cols(
                    w, ch, slice(inner + g.start, inner + g.stop),
                    slice(inner + gn + g.start, inner + gn + g.stop))

            share = {
                "ssm_in": _cols(
                    full["ssm_in"], ch,
                    *(slice(inner + s.start, inner + s.stop) for s in (
                        ch, slice(inner + g.start, inner + g.stop),
                        slice(inner + gn + g.start, inner + gn + g.stop))),
                    slice(2 * inner + 2 * gn + h.start,
                          2 * inner + 2 * gn + h.stop)),
                "ssm_conv_w": conv(full["ssm_conv_w"]),
                "ssm_conv_b": conv(full["ssm_conv_b"]),
                "ssm_dt_bias": full["ssm_dt_bias"][h],
                "ssm_a_log": full["ssm_a_log"][h], "ssm_d": full["ssm_d"][h],
                "ssm_norm": full["ssm_norm"][ch],
                "ssm_out": full["ssm_out"][ch],
            }
            parts.append(ref.mamba(
                a, share, mamba_n_heads=MH // RANKS, mamba_n_groups=1, **kw))
            # the PROGRAM's mixer on the share is the reference's
            got, _ = ssd.mamba_mixer(
                share, a[None], n_heads=MH // RANKS, head_dim=MP,
                d_state=MN, n_groups=1, chunk=8)
            np.testing.assert_allclose(got[0], parts[-1], rtol=1e-4,
                                       atol=1e-5)
    np.testing.assert_allclose(sum(parts), whole, rtol=1e-5, atol=1e-6)
    assert float(jnp.abs(parts[0] - whole).max()) > 1e-3    # a PART


def test_attentions_head_shares_add_up_to_the_uncut_block(a):
    """4 ranks: 2 of 8 query heads each over ONE of the 2 key/value
    heads (each lies on 2 ranks, never cut)."""
    full = {"wq": _normal(1, D, HQ * HD), "wk": _normal(2, D, HKV * HD),
            "wv": _normal(3, D, HKV * HD), "wo": _normal(4, HQ * HD, D)}
    with jax.default_matmul_precision("highest"):
        whole = ref.attention(a, full, n_heads=HQ, n_kv_heads=HKV, head_dim=HD)
        parts = []
        for r in range(RANKS):
            q = slice(r * HQ // RANKS * HD, (r + 1) * HQ // RANKS * HD)
            kv = r * HKV // RANKS               # the rank's one KV head
            kv = slice(kv * HD, (kv + 1) * HD)
            share = {"wq": full["wq"][:, q], "wk": full["wk"][:, kv],
                     "wv": full["wv"][:, kv], "wo": full["wo"][q]}
            parts.append(ref.attention(
                a, share, n_heads=HQ // RANKS, n_kv_heads=1, head_dim=HD))
    np.testing.assert_allclose(sum(parts), whole, rtol=1e-5, atol=1e-6)
    assert float(jnp.abs(parts[0] - whole).max()) > 1e-3


def test_the_expert_shares_add_up_to_the_uncut_block(a):
    """4 ranks of 4 of 16 experts: the routed parts, each routed over
    ALL 16, add up to the uncut layer's routed part; the shared expert
    (whole on every rank) is counted once.  The PROGRAM's layer on
    share 0 is the reference's share 0."""
    from theanompi_tpu.parallel.moe import moe_ffn, shared_expert

    full = {"router": _normal(1, D, E), "w_lat_down": _normal(2, D, DL),
            "w_lat_up": _normal(3, DL, D), "we_up": _normal(4, E, DL, F),
            "we_down": _normal(5, E, F, DL), "ws_up": _normal(6, D, FS),
            "ws_down": _normal(7, FS, D)}
    bias = 0.2 * _normal(8, E)
    kw = dict(top_k=K, route_scale=5.0, select_bias=bias)
    with jax.default_matmul_precision("highest"):
        whole = ref.routed(a, full, **kw) + ref.shared(a, full)
        shares = [
            dict(full, we_up=full["we_up"][r * 4:(r + 1) * 4],
                 we_down=full["we_down"][r * 4:(r + 1) * 4])
            for r in range(RANKS)
        ]
        parts = [ref.routed(a, lp, first=4 * r, **kw)
                 for r, lp in enumerate(shares)]
        np.testing.assert_allclose(
            sum(parts) + ref.shared(a, full), whole, rtol=1e-5, atol=1e-5)
        assert float(jnp.abs(parts[0]).max()) > 1e-3
        lp = shares[0]
        y, aux = moe_ffn(
            a[None], lp["router"], None, lp["we_up"], lp["we_down"],
            n_experts=E, top_k=K, capacity_factor=None, expert_axis=None,
            model_axis=None, scoring="sigmoid", select_bias=bias,
            route_scale=5.0, held=4,
            latent=(lp["w_lat_down"], lp["w_lat_up"]))
        np.testing.assert_allclose(y[0], parts[0], rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(
            shared_expert(a, None, lp["ws_up"], lp["ws_down"], None),
            ref.shared(a, full), rtol=1e-5, atol=1e-6)
    assert float(aux["dropped"]) == 0


# -- the stack block by block ------------------------------------------------


def _small(**over):
    from benchmark.drivers.train import program_config
    from benchmark.run import load_cell

    config = load_cell(CELL)["config"]
    config = dict(config, **config["rehearsal"])
    return config, dict(program_config(config, seed=SEED, n_replicas=1),
                        optimizer="sgd", device_data_cache=False, **over)


def _weights_and_batch(cfg):
    from theanompi_tpu.parallel import make_mesh

    model = Llama(cfg)
    model.build_model(n_replicas=1)
    model.compile_iter_fns(mesh=make_mesh(data=1, devices=jax.devices()[:1]))
    p0 = jax.tree.map(np.asarray, jax.device_get(model.params))
    model.data.shuffle(0)
    batch = tuple(np.asarray(x) for x in model.data.train_batch(0))
    return model, p0, batch


@pytest.mark.parametrize("pattern", ["M*", "MM*E*ME", "*E", "EM"])
def test_any_order_of_blocks_equals_the_reference(pattern):
    """A mixer directly followed by attention (``M*``: two mixers, no
    FFN between them), two mixers in a row, attention then an expert
    block, a stack that starts with one."""
    config, cfg = _small(layer_types=pattern, n_layers=len(pattern),
                         **({} if "E" in pattern else dict(
                             n_experts=0, moe_experts_held=None,
                             moe_shared_experts=0, moe_latent_dim=None,
                             moe_bias_rate=0.0)))
    model, p0, batch = _weights_and_batch(cfg)
    assert model.block_pattern == pattern
    assert [sorted(k for k in lp if k.endswith("_norm") and "ssm" not in k)
            for lp in p0["layers"]] == [
        ["mlp_norm"] if b == "E" else ["attn_norm"] for b in pattern]
    kw = dict(config["reference"]["kwargs"], pattern=pattern)
    with jax.default_matmul_precision("highest"):
        want, grads = jax.value_and_grad(ref.loss)(p0, *batch, **kw)
        if "E" in pattern:
            bias0 = np.zeros((pattern.count("E"), 16), np.float32)
            loss, got, *_ = nemotron_check._program_step(
                config, cfg, (), p0, bias0, batch)
        else:
            rate = np.float32(2.0 ** 10)
            p1, _, _, loss, _, _ = model._train_step(
                model.params, model.opt_state, model.ef_state,
                *model.put_batch(batch), rate)
            got = jax.tree.map(lambda x, y: (x - np.asarray(y)) / rate,
                               p0, jax.device_get(p1))
    assert abs(float(loss) - float(want)) < 1e-5 * float(want)
    flat, want_flat = (nemotron_check._flat(t) for t in (got, grads))
    assert set(flat) == set(want_flat)
    for leaf, g in flat.items():
        assert glm_check._rel0(g, want_flat[leaf]) < 1e-3, leaf


def test_a_mamba_LAYER_may_now_hold_an_expert_ffn():
    """``layer_types`` names whole layers: a mamba layer's FFN half is
    what ``n_experts`` says, an expert layer too (refused until this
    PR).  Both counters ride out of the step."""
    from theanompi_tpu.parallel import make_mesh

    model = Llama(dict(
        dim=32, n_layers=2, n_heads=4, n_kv_heads=2, ffn_dim=16, vocab=64,
        seq_len=16, batch_size=2, layer_types=["mamba", "attention"],
        mamba_n_heads=4, mamba_d_head=16, mamba_d_state=8, mamba_chunk_size=8,
        n_experts=4, moe_top_k=2, capacity_factor=None, optimizer="sgd",
        compute_dtype="float32"))
    assert model.layer_kinds == ("moe", "moe")
    model.build_model(n_replicas=1)
    model.compile_iter_fns(mesh=make_mesh(data=1, devices=jax.devices()[:1]))
    model.data.shuffle(0)
    _, _, _, loss, _, routing, ssm = model._train_step(
        model.params, model.opt_state, model.ef_state,
        *model.put_batch(model.data.train_batch(0)), np.float32(0.1))
    assert np.isfinite(float(loss))
    assert routing.shape == (2, 5) and ssm.shape == (1, 2)


def test_blocks_and_latent_two_product_experts_compose_with_tp():
    """Without a mamba block or a head share the new knobs run under
    tensor parallelism: the experts' width over the model axis (the
    down product's psum closes it), the latent pair and the router
    replicated; the step equals the one-device step."""
    from theanompi_tpu.parallel import make_mesh

    base = dict(
        dim=32, n_layers=3, n_heads=4, n_kv_heads=2, ffn_dim=16, vocab=64,
        seq_len=16, batch_size=2, layer_types="*E*", n_experts=4,
        moe_top_k=2, capacity_factor=None, moe_shared_experts=1,
        moe_shared_dim=32, moe_latent_dim=16, hidden_act="relu2",
        optimizer="sgd", compute_dtype="float32")
    got = {}
    for tp in (1, 2):
        model = Llama(dict(base, tp=tp))
        model.build_model(n_replicas=1)
        model.compile_iter_fns(mesh=make_mesh(
            data=1, model=tp, devices=jax.devices()[:tp]))
        if tp == 1:
            p0 = jax.device_get(model.params)
        model.params = jax.device_put(p0, model._shardings(model._specs))
        model.data.shuffle(0)
        p1, _, _, loss, *_ = model._train_step(
            model.params, model.opt_state, model.ef_state,
            *model.put_batch(model.data.train_batch(0)), np.float32(0.5))
        got[tp] = float(loss), jax.device_get(p1)
    assert got[1][0] == pytest.approx(got[2][0], rel=1e-6)
    for a, b in zip(jax.tree.leaves(got[1][1]), jax.tree.leaves(got[2][1])):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


def test_the_file_declares_the_shares_beside_the_published_counts():
    _, cfg = _small()
    model = Llama(cfg)
    assert model.head_share == {
        "n_heads": (2, 8), "n_kv_heads": (1, 2),
        "mamba_n_heads": (2, 8), "mamba_n_groups": (1, 4)}
    assert (model.n_heads, model.n_kv_heads) == (2, 1)
    assert model._mamba["n_heads"] == 2 and model._mamba["n_groups"] == 1
    assert model.block_kinds_count == {"*": 1, "E": 5, "M": 5}
    assert model.mixer_kinds_count == {"attention": 1, "mamba": 5}
    assert model.attention_kinds == {"full_attention": 1}
    assert model.moe_calls == 5 and model.remat_calls == 11
    assert model.layer_kinds.count("moe") == 5
    # what a kept expert call holds: the sorted rows in the LATENT and
    # ONE product, and the sort's two results
    picks = 4 * 2 * 32
    rows = held_rows_bound(picks, 4, 16)
    assert model.remat_kept_moe_bytes_per_call == (
        rows * (32 + 48) * 4 + 2 * picks * 4)
    shapes = jax.eval_shape(model._init_full_params, jax.random.PRNGKey(0))
    assert shapes["layers"][1]["we_up"].shape == (4, 32, 48)
    assert shapes["layers"][1]["router"].shape == (64, 16)
    assert shapes["layers"][7]["wq"].shape == (64, 2 * 16)
    assert shapes["layers"][0]["ssm_out"].shape == (2 * 16, 64)


@pytest.mark.parametrize("picks, held, n_experts, want", [
    (22 * 16384, 8, 512, 11264),        # the cell: twice 5632
    (22 * 8192, 8, 512, 5632),
    (22 * 16384, 512, 512, 22 * 16384),
    (22 * 16384, None, 512, 22 * 16384),
])
def test_held_rows_bound_at_22_of_512(picks, held, n_experts, want):
    assert held_rows_bound(picks, held, n_experts) == want
    if held and held < n_experts:
        assert want % 512 == 0 and want >= 2 * picks * held // n_experts


# -- what the new knobs refuse -----------------------------------------------

PLAIN = dict(dim=32, n_layers=2, n_heads=4, n_kv_heads=2, ffn_dim=64,
             vocab=64, seq_len=16, batch_size=2)
MAMBA = dict(mamba_n_heads=4, mamba_d_head=16, mamba_d_state=8)


@pytest.mark.parametrize("over, sentence", [
    (dict(n_heads=8, n_kv_heads=4, n_heads_held=4, n_kv_heads_held=2, tp=2),
     "a head share (n_heads_held, mamba_heads_held"),
    (dict(n_heads_held=2, n_kv_heads_held=1, n_heads_per_layer=[4, 4]),
     "a head share (n_heads_held, mamba_heads_held"),
    (dict(hidden_act="relu2"), "hidden_act: relu2 (two products"),
    (dict(hidden_act="relu2", n_experts=4, first_k_dense=1),
     "hidden_act: relu2 (two products"),
    (dict(layer_types="*-"), "a block pattern (layer_types as a string"),
    (dict(layer_types="**", mtp_depth=1),
     "a block pattern (layer_types as a string"),
    (dict(layer_types="M*", tp=2, **MAMBA),
     "a mamba layer or block (layer_types) does not yet compose"),
    (dict(layer_types="**", pp=2),
     "layer_types (an attention kind, a window, a rotary"),
], ids=["share_tp", "share_per_layer", "relu2_dense", "relu2_first_dense",
        "dense_block", "pattern_mtp", "mamba_block_tp", "pattern_pp"])
def test_the_new_knobs_refuse_what_they_do_not_run(over, sentence):
    with pytest.raises(NotImplementedError) as e:
        Llama(dict(PLAIN, **over))
    assert sentence in str(e.value)
    doc = (ROOT / "docs/REFUSALS.md").read_text()
    assert (sentence in doc
            or "does not yet compose with pipeline parallelism" in doc)


@pytest.mark.parametrize("over", [
    dict(layer_types="*X"),                         # an unknown kind
    dict(layer_types="*"),                          # too few blocks
    dict(layer_types="*E"),                         # 'E' without experts
    dict(layer_types="**", n_experts=4),            # experts without 'E'
    dict(layer_types="**", first_k_dense=1, n_experts=4),
    dict(n_heads_held=3, n_kv_heads_held=1),        # no whole share
    dict(n_heads_held=2, n_kv_heads_held=2),        # 2 ranks: 1 KV head each
    dict(layer_types=["mamba"] * 2, mamba_heads_held=2, mamba_groups_held=1,
         mamba_n_groups=4, **MAMBA),                # 2 ranks: 2 groups each
    dict(hidden_act="gelu"),
], ids=["unknown", "short", "E_alone", "experts_alone", "both", "ragged",
        "kv", "groups", "act"])
def test_a_value_that_describes_nothing_is_a_value_error(over):
    with pytest.raises(ValueError):
        Llama(dict(PLAIN, **over))


def test_the_published_counts_still_tie_the_scan_to_the_width():
    with pytest.raises(AssertionError, match="a head share"):
        Llama(dict(PLAIN, layer_types=["mamba"] * 2, mamba_n_heads=2,
                   mamba_d_head=16, mamba_d_state=8))


def test_serving_refuses_blocks_shares_and_latent_experts():
    _, cfg = _small()
    model, _, _ = _weights_and_batch(cfg)
    with pytest.raises(NotImplementedError) as e:
        model.make_decoder(max_slots=2, max_seq=32)
    assert "serving runs whole layers at whole head counts" in str(e.value)
    assert "serving runs whole layers at whole head counts" in (
        ROOT / "docs/REFUSALS.md").read_text()


def test_defaults_leave_an_older_decoder_as_it_was():
    plain = Llama({})
    assert plain.block_pattern is None and plain.block_kinds_count is None
    assert plain.head_share == {} and plain.hidden_act == "silu"
    assert plain.moe_latent_dim is None
    specs = Llama(dict(PLAIN, n_experts=4, moe_shared_experts=1,
                       capacity_factor=None)).param_specs()["layers"][0]
    assert {"we_gate", "ws_gate", "attn_norm", "mlp_norm"} <= set(specs)
    assert not {"w_lat_down", "w_lat_up"} & set(specs)
