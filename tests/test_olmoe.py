"""OLMoE through ``Llama``: QK-norm, un-renormalised top-k gates and
the dropless expert path, held to the plain reference the chip check
uses (``benchmark/reference/olmoe.py``: float32, no sort, no
capacity — every expert on every token under a dense gate matrix).

Small widths, float32, on the CPU.  Tolerances, and why:

- logits ``2e-5`` of the largest logit, loss ``1e-5`` relative,
  gradients ``1e-4`` of a leaf's norm: program and reference do the
  same float32 sums in different orders (sorted rows against a dense
  gate matrix; the flash kernel's fallback math against a plain
  softmax).  Measured here: logits 6.6e-7, loss equal to the last
  digit, the worst leaf's gradient 1.1e-6 (the router's; read back
  from an SGD step of the real train step at a power-of-two rate).
- Each of the three wrong architectures this PR could ship by
  accident — renormalised gates, a dropped pick, no QK-norm — moves
  the logits by 0.30, 0.45 and 0.65 of the largest and must FAIL the
  same check, here by a margin of fifty tolerances.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import olmoe as ref
from theanompi_tpu import obs
from theanompi_tpu.models.llama import Llama
from theanompi_tpu.parallel import make_mesh
from theanompi_tpu.parallel.moe import moe_ffn
from theanompi_tpu.utils import Recorder

E, K = 8, 3
SMALL = dict(
    dim=64, n_layers=2, n_heads=4, n_kv_heads=4, ffn_dim=32, vocab=96,
    seq_len=32, batch_size=2, n_train=8, n_val=1, seed=5,
    compute_dtype="float32", remat=True, optimizer="sgd",
    n_experts=E, moe_top_k=K, capacity_factor=None,
    moe_renormalize=False, qk_norm=True,
    # larger than the published 0.01 / 0.001, so that a wrong router
    # term moves the loss and the router's gradient far past tolerance
    moe_aux_coef=0.1, moe_z_coef=0.01,
)
REF_KW = dict(n_heads=4, n_kv_heads=4, top_k=K, aux_coef=0.1, z_coef=0.01)
LOGIT_TOL, LOSS_RTOL, GRAD_RTOL = 2e-5, 1e-5, 1e-4
LR = 64.0       # a power of two: p - LR * g loses no digit of g to LR


def build(devices, *, tp=1, **over):
    m = Llama(dict(SMALL, tp=tp, **over))
    m.build_model(n_replicas=1)
    m.compile_iter_fns(mesh=make_mesh(model=tp, devices=devices[:tp]))
    return m


def perturbed(params, seed=11):
    """The initial weights with the norm vectors moved off 1.0, so
    that a q_norm applied to the wrong tensor, or not at all, shows."""
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    return jax.tree.unflatten(tree, [
        a * (1 + 0.3 * jax.random.normal(k, a.shape)) if a.ndim == 1 else a
        for a, k in zip(leaves, keys)
    ])


def program_logits(m, params, x):
    batch = m._batch_sharding.spec          # as the val step shards ids
    fwd = jax.jit(jax.shard_map(
        lambda p, ids: m._forward(p, ids), mesh=m.mesh,
        in_specs=(m._specs, batch), out_specs=jax.P(*batch, "model"),
    ))
    return np.asarray(fwd(params, x))


@pytest.fixture(scope="module")
def batch():
    rng = np.random.default_rng(0)
    x = rng.integers(0, SMALL["vocab"], (2, SMALL["seq_len"]), dtype=np.int32)
    y = rng.integers(0, SMALL["vocab"], (2, SMALL["seq_len"]), dtype=np.int32)
    return x, y


@pytest.fixture(scope="module")
def held(devices8, batch):
    """One SGD step of the program's own train step on seeded weights,
    and the reference's loss and gradients on the same weights."""
    x, y = batch
    m = build(devices8)
    # host copies: the step donates what it is given
    p0 = jax.tree.map(np.asarray, perturbed(jax.device_get(m.params)))
    placed = jax.device_put(p0, jax.tree.map(lambda a: a.sharding, m.params))
    logits = program_logits(m, placed, x)
    p1, _, _, loss, _, routing = m._train_step(
        placed, m.opt_state, m.ef_state, *m.put_batch((x, y)),
        jnp.float32(LR),
    )
    grads = jax.tree.map(lambda a, b: (np.asarray(a) - np.asarray(b)) / LR,
                         p0, jax.device_get(p1))
    ref_loss, ref_grads = jax.value_and_grad(
        lambda p: ref.loss(p, x, y, **REF_KW)
    )(p0)
    ref_logits, ref_picks = zip(*(
        ref.logits_at(p0, ids, np.arange(len(ids)), **REF_KW) for ids in x
    ))
    return {
        "model": m, "params": p0, "logits": logits, "loss": float(loss),
        "grads": grads, "routing": np.asarray(routing),
        "ref_loss": float(ref_loss), "ref_grads": jax.device_get(ref_grads),
        "ref_logits": np.stack(ref_logits), "ref_picks": np.stack(ref_picks),
    }


def logits_off(got, want):
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


class TestAgainstReference:
    def test_logits(self, held):
        assert logits_off(held["logits"], held["ref_logits"]) < LOGIT_TOL

    def test_loss_with_both_router_terms(self, held):
        assert held["loss"] == pytest.approx(held["ref_loss"], rel=LOSS_RTOL)

    @pytest.mark.parametrize("leaf", [
        "embed", "final_norm", "lm_head", "attn_norm", "wq", "wk", "wv",
        "wo", "q_norm", "k_norm", "mlp_norm", "router", "we_gate", "we_up",
        "we_down",
    ])
    def test_gradient(self, held, leaf):
        if leaf in held["grads"]:
            pairs = [(held["grads"][leaf], held["ref_grads"][leaf])]
        else:
            pairs = [(g[leaf], r[leaf]) for g, r in zip(
                held["grads"]["layers"], held["ref_grads"]["layers"])]
        for got, want in pairs:
            want = np.asarray(want)
            assert np.linalg.norm(want) > 0
            rel = np.linalg.norm(got - want) / np.linalg.norm(want)
            assert rel < GRAD_RTOL, (leaf, rel)

    def test_router_terms_move_the_loss(self, held, batch):
        plain = float(ref.loss(held["params"], *batch,
                               **dict(REF_KW, aux_coef=0.0, z_coef=0.0)))
        assert held["ref_loss"] - plain > 0.1      # LB ~ 1, Z ~ 4

    @pytest.mark.parametrize("wrong", [
        dict(moe_renormalize=True),         # Mixtral's gates
        dict(capacity_factor=0.5),          # capacity buffers that drop
        dict(qk_norm=False),                # no norm on q and k
    ], ids=["renormalised", "dropping", "no_qk_norm"])
    def test_wrong_architecture_fails_the_check(self, devices8, held, batch,
                                                wrong):
        m = build(devices8, **wrong)
        params = held["params"]
        if "qk_norm" in wrong:
            params = dict(params, layers=[
                {k: v for k, v in lp.items() if k not in ("q_norm", "k_norm")}
                for lp in params["layers"]
            ])
        got = program_logits(m, params, batch[0])
        assert logits_off(got, held["ref_logits"]) > 50 * LOGIT_TOL

    def test_tp2_is_a_layout_not_other_maths(self, devices8, held, batch):
        """FFN width, heads and the QK-norm statistic sharded over the
        model axis: the same logits (the statistic is psum'd)."""
        m = build(devices8, tp=2)
        got = program_logits(m, held["params"], batch[0])
        assert logits_off(got, held["logits"]) < 1e-5


class TestCounters:
    def test_step_counters_match_the_reference_picks(self, held):
        routing = held["routing"]                       # [L, E+1]
        assert routing.shape == (SMALL["n_layers"], E + 1)
        picks = 2 * SMALL["seq_len"] * K
        rows = np.stack([
            np.bincount(layer.ravel(), minlength=E)
            for layer in np.swapaxes(held["ref_picks"], 0, 1)
        ])                                              # [L, E]
        np.testing.assert_array_equal(
            np.rint(routing[:, :E] * picks).astype(int), rows)
        assert np.all(routing[:, -1] == 0)
        got = obs.routing.moe_counters(routing, picks)
        assert got["moe_dropped_picks"] == 0
        assert got["moe_rows_per_expert"] == rows.tolist()
        assert got["moe_load_max_over_mean"] == pytest.approx(
            (rows.max(1) / rows.mean(1)).max())
        assert obs.last_moe_counters() is got

    def test_recorder_reads_them_at_its_fence(self, devices8):
        m = build(devices8, steps_per_call=2, device_data_cache=True,
                  n_train=4, optimizer="adam", lr=1e-3)
        rec = Recorder(verbose=False)
        m.data.shuffle(0)
        m.train_chunk(0, 2, rec)
        assert rec.moe_counters is None     # a device value until the fence
        rec.fence()
        c = rec.moe_counters
        assert c["moe_dropped_picks"] == 0
        assert c["moe_picks_per_step"] == 2 * SMALL["seq_len"] * K
        assert np.sum(c["moe_rows_per_expert"], axis=1).tolist() == [
            c["moe_picks_per_step"]] * SMALL["n_layers"]
        assert 1.0 <= c["moe_load_max_over_mean"] <= E
        assert "tm_train_moe_dropped_picks 0" in rec.metrics_txt()

    def test_capacity_path_counts_its_drops(self, devices8):
        m = build(devices8, capacity_factor=0.25)
        rec = Recorder(verbose=False)
        m.train_iter(0, rec)
        rec.fence()
        assert rec.moe_counters["moe_dropped_picks"] > 0

    def test_dense_model_has_none(self, devices8):
        m = build(devices8, n_experts=0)
        rec = Recorder(verbose=False)
        m.train_iter(0, rec)
        rec.fence()
        assert rec.moe_counters is None


class TestDroplessFfn:
    """``moe_ffn`` alone, no mesh."""

    D, F, N = 16, 24, 40

    def mats(self, router_bias=None):
        ks = jax.random.split(jax.random.PRNGKey(2), 5)
        router = jax.random.normal(ks[0], (self.D, E)) * 0.2
        if router_bias is not None:
            router = router * 1e-3 + router_bias
        return dict(
            x=jax.random.normal(ks[1], (2, self.N // 2, self.D)),
            w_router=router,
            we_gate=jax.random.normal(ks[2], (E, self.D, self.F)) * 0.3,
            we_up=jax.random.normal(ks[3], (E, self.D, self.F)) * 0.3,
            we_down=jax.random.normal(ks[4], (E, self.F, self.D)) * 0.3,
        )

    def run(self, m, **kw):
        return moe_ffn(
            m["x"], m["w_router"], m["we_gate"], m["we_up"], m["we_down"],
            n_experts=E, top_k=K, expert_axis=None, model_axis=None, **kw)

    def reference(self, m, renormalize=False):
        h = m["x"].reshape(-1, self.D)
        gate, idx, _, _ = ref.route(h, m["w_router"], K)
        if renormalize:
            gate = gate / gate.sum(-1, keepdims=True)
        lp = {k: m[k] for k in ("we_gate", "we_up", "we_down")}
        with jax.default_matmul_precision("highest"):
            return np.asarray(ref._experts(h, gate, lp)), np.asarray(idx)

    @pytest.mark.parametrize("renormalize", [False, True])
    def test_equals_the_capacity_path_with_room_for_all(self, renormalize):
        """At ``capacity_factor = E/k`` the buffers hold every pick.
        To 1e-6 and not bitwise: a batched product over padded
        buffers and a grouped one over sorted rows round apart."""
        m = self.mats()
        y, aux = self.run(m, capacity_factor=None, renormalize=renormalize)
        y_cap, aux_cap = self.run(m, capacity_factor=E / K,
                                  renormalize=renormalize)
        np.testing.assert_allclose(y, y_cap, atol=1e-6, rtol=1e-6)
        for k in ("f", "p", "lb", "z", "dropped"):
            np.testing.assert_array_equal(aux[k], aux_cap[k])
        want, _ = self.reference(m, renormalize)
        np.testing.assert_allclose(y.reshape(-1, self.D), want,
                                   atol=2e-6, rtol=1e-5)

    def test_gradients_equal_the_capacity_path(self):
        m = self.mats()
        keys = ("x", "w_router", "we_gate", "we_up", "we_down")

        def total(cf, *args):
            y, aux = self.run(dict(zip(keys, args)), capacity_factor=cf,
                              renormalize=False)
            return jnp.sum(y * y) + aux["lb"] + aux["z"]

        args = [m[k] for k in keys]
        got = jax.grad(lambda *a: total(None, *a), range(5))(*args)
        want = jax.grad(lambda *a: total(E / K, *a), range(5))(*args)
        for g, w in zip(got, want):
            np.testing.assert_allclose(g, w, atol=1e-5, rtol=1e-5)

    def test_unrenormalised_gates_sum_to_less_than_one(self):
        m = self.mats()
        gate, _, _, _ = ref.route(m["x"].reshape(-1, self.D),
                                  m["w_router"], K)
        assert np.all(np.asarray(gate.sum(-1)) < 0.99)
        y, _ = self.run(m, capacity_factor=None, renormalize=False)
        y_norm, _ = self.run(m, capacity_factor=None, renormalize=True)
        assert np.max(np.abs(y - y_norm)) > 1e-2

    def test_skewed_routing_drops_nothing(self):
        """Expert 0 is every token's first pick (N rows, five times the
        balanced share), expert 7 is nobody's: a group of N rows and a
        group of none, and still every pick is computed."""
        bias = jnp.zeros((self.D, E)).at[:, 0].set(0.5).at[:, 7].set(-0.5)
        m = self.mats(router_bias=bias)
        m["x"] = jnp.abs(m["x"])        # so the bias decides for all
        y, aux = self.run(m, capacity_factor=None, renormalize=False)
        want, idx = self.reference(m)
        rows = np.bincount(idx.ravel(), minlength=E)
        assert rows[0] == self.N and rows[7] == 0
        np.testing.assert_allclose(y.reshape(-1, self.D), want,
                                   atol=2e-6, rtol=1e-5)
        np.testing.assert_allclose(np.asarray(aux["f"]) * self.N * K, rows,
                                   atol=1e-4)
        assert float(aux["dropped"]) == 0.0
        # the capacity path at its default factor loses picks here
        _, aux_cap = self.run(m, capacity_factor=1.25, renormalize=False)
        assert float(aux_cap["dropped"]) > 0


class TestRefusal:
    def test_dropless_with_expert_parallelism_is_refused(self):
        with pytest.raises(NotImplementedError, match="ragged all-to-all"):
            Llama(dict(SMALL, ep=2))

    def test_and_listed(self):
        from pathlib import Path

        doc = Path(__file__).resolve().parent.parent / "docs" / "REFUSALS.md"
        assert "dropless MoE (capacity_factor: null)" in doc.read_text()

    def test_serving_refuses_what_it_would_compute_wrong(self, devices8):
        """The decoders know neither experts nor QK-norm: a model with
        either is refused, never served without them."""
        for over in (dict(), dict(n_experts=0)):        # OLMoE; dense + QK-norm
            with pytest.raises(NotImplementedError, match="not yet servable"):
                build(devices8, **over).make_decoder(max_slots=2, max_seq=32)
