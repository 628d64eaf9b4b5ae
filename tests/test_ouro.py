"""The looped decoder through ``Llama`` (``ut_steps`` R > 1: one stack
of layers run R times over the same weights, sandwich norms, a loss
at every exit under a learned exit distribution), held to the plain
reference the chip check uses (``benchmark/reference/ouro.py``:
float32, Python loops over passes and layers, no scan, no kernels).

Small widths, float32, on the CPU.  Tolerances, and why:

- loss ``1e-6`` relative, gradients ``2e-5`` of a leaf's norm:
  program and reference do the same float32 sums in different orders
  (the flash kernel's fallback math against a plain softmax, a scan's
  accumulation of a shared weight's four gradients against autodiff's
  sum, ``log_sigmoid`` sums against products of ``1 - lam``).
  Measured here: loss 1e-7, the worst leaf's gradient 2.8e-6 (read
  back from an SGD step of the real train step at a power-of-two
  rate).
- bfloat16 compute where float32 is stated moves the loss by 2e-3 and
  every matrix leaf's gradient by 1e-2 or more of its norm, and must
  FAIL both checks, here by a thousand and five hundred tolerances.
"""

from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import theanompi_tpu
from benchmark.reference import decoder as plain_ref
from benchmark.reference import ouro as ref
from theanompi_tpu import obs
from theanompi_tpu.models.llama import Llama
from theanompi_tpu.parallel import make_mesh
from theanompi_tpu.utils import Recorder

R, L = 3, 2
SMALL = dict(
    dim=64, n_layers=L, n_heads=4, n_kv_heads=4, ffn_dim=96, vocab=96,
    seq_len=32, batch_size=2, n_train=8, n_val=1, seed=5,
    compute_dtype="float32", remat=True, optimizer="sgd",
    ut_steps=R, sandwich_norm=True, exit_beta=0.1,
    rope_theta=1e6, norm_eps=1e-6,
)
REF_KW = dict(n_heads=4, n_kv_heads=4, ut_steps=R, beta=0.1,
              rope_theta=1e6, eps=1e-6)
LOSS_RTOL, GRAD_RTOL = 1e-6, 2e-5
LR = 64.0       # a power of two: p - LR * g loses no digit of g to LR
LAYER_LEAVES = ["attn_norm", "wq", "wk", "wv", "wo", "attn_out_norm",
                "mlp_norm", "w_gate", "w_up", "w_down", "mlp_out_norm"]
TOP_LEAVES = ["embed", "final_norm", "lm_head", "exit_gate_w", "exit_gate_b"]


def build(devices, *, tp=1, **over):
    m = Llama(dict(SMALL, tp=tp, **over))
    m.build_model(n_replicas=1)
    m.compile_iter_fns(mesh=make_mesh(model=tp, devices=devices[:tp]))
    return m


def perturbed(params, seed=11):
    """The initial weights with the norm vectors moved off 1.0 (a norm
    on the wrong tensor, or none, then shows) and a gate that is far
    from even: weight 5x, bias 0.3."""
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    p = jax.tree.unflatten(tree, [
        a * (1 + 0.3 * jax.random.normal(k, a.shape)) if a.ndim == 1 else a
        for a, k in zip(leaves, keys)
    ])
    return dict(p, exit_gate_w=5 * p["exit_gate_w"],
                exit_gate_b=jnp.full((1,), 0.3))


def sgd_step(m, p0, x, y):
    """One SGD step of the program's own train step from the host
    weights ``p0`` -> (loss, gradients read back from the update,
    the step's exit counters)."""
    placed = jax.device_put(p0, jax.tree.map(lambda a: a.sharding, m.params))
    p1, _, _, loss, _, counters = m._train_step(
        placed, m.opt_state, m.ef_state, *m.put_batch((x, y)),
        jnp.float32(LR),
    )
    grads = jax.tree.map(lambda a, b: (np.asarray(a) - np.asarray(b)) / LR,
                         p0, jax.device_get(p1))
    return float(loss), grads, np.asarray(counters)


def leaf_pairs(got, want, leaf):
    if leaf in got:
        return [(got[leaf], want[leaf])]
    return [(g[leaf], w[leaf]) for g, w in zip(got["layers"], want["layers"])]


def worst_rel(got, want, leaves=LAYER_LEAVES + TOP_LEAVES):
    return max(
        float(np.linalg.norm(g - np.asarray(w)) / np.linalg.norm(w))
        for leaf in leaves for g, w in leaf_pairs(got, want, leaf)
    )


@pytest.fixture(scope="module")
def batch():
    rng = np.random.default_rng(0)
    x = rng.integers(0, SMALL["vocab"], (2, SMALL["seq_len"]), dtype=np.int32)
    y = rng.integers(0, SMALL["vocab"], (2, SMALL["seq_len"]), dtype=np.int32)
    return x, y


@pytest.fixture(scope="module")
def held(devices8, batch):
    """One SGD step of the program on seeded weights, and the
    reference's loss and gradients on the same weights."""
    m = build(devices8)
    # host copies: the step donates what it is given
    p0 = jax.tree.map(np.asarray, perturbed(jax.device_get(m.params)))
    loss, grads, counters = sgd_step(m, p0, *batch)
    ref_loss, ref_grads = jax.value_and_grad(
        lambda p: ref.loss(p, *batch, **REF_KW)
    )(p0)
    return {"model": m, "params": p0, "loss": loss, "grads": grads,
            "counters": counters, "ref_loss": float(ref_loss),
            "ref_grads": jax.device_get(ref_grads)}


class TestAgainstReference:
    def test_first_step_loss(self, held):
        assert held["loss"] == pytest.approx(held["ref_loss"], rel=LOSS_RTOL)

    @pytest.mark.parametrize("leaf", LAYER_LEAVES + TOP_LEAVES)
    def test_gradient(self, held, leaf):
        for got, want in leaf_pairs(held["grads"], held["ref_grads"], leaf):
            assert np.linalg.norm(want) > 0
            assert worst_rel({leaf: got}, {leaf: want}, [leaf]) < GRAD_RTOL

    def test_one_set_of_layer_parameters(self, held):
        """R passes, L layers' worth of leaves: in the parameters, the
        exchange plan's count and (sgd keeps no state) nothing else."""
        m = held["model"]
        assert len(held["params"]["layers"]) == L
        n = sum(a.size for a in jax.tree.leaves(held["params"]))
        assert m.exchange.n_elems == n

    def test_the_exit_terms_move_the_loss(self, held, batch):
        """The loss is not the last exit's cross-entropy, nor the
        weighted sum without its entropy term."""
        no_entropy = float(ref.loss(held["params"], *batch,
                                    **dict(REF_KW, beta=0.0)))
        assert no_entropy - held["ref_loss"] > 0.05      # beta * H(q) ~ 0.08
        assert abs(held["counters"][2 * R - 1] - held["ref_loss"]) > 0.05

    @pytest.mark.parametrize("wrong", [
        dict(sandwich_norm=False),      # a plain pre-norm block
        dict(rope_theta=1e4),           # the repo's old constant
        dict(norm_eps=1e-2),
        dict(exit_beta=0.0),
    ], ids=["no_sandwich", "theta", "eps", "beta"])
    def test_wrong_architecture_fails_the_check(self, devices8, held, batch,
                                                wrong):
        m = build(devices8, **wrong)
        p0 = held["params"]
        if "sandwich_norm" in wrong:
            p0 = dict(p0, layers=[
                {k: v for k, v in lp.items() if not k.endswith("out_norm")}
                for lp in p0["layers"]
            ])
        loss, _, _ = sgd_step(m, p0, *batch)
        assert abs(loss - held["ref_loss"]) > 50 * LOSS_RTOL * held["ref_loss"]

    def test_bfloat16_compute_fails_both_tolerances(self, devices8, held,
                                                    batch):
        loss, grads, _ = sgd_step(
            build(devices8, compute_dtype="bfloat16"), held["params"], *batch
        )
        assert abs(loss / held["ref_loss"] - 1) > 100 * LOSS_RTOL
        matrices = [k for k in LAYER_LEAVES if k.startswith("w")]
        assert worst_rel(grads, held["ref_grads"], matrices) > 100 * GRAD_RTOL


class TestSharing:
    @pytest.mark.parametrize("leaf", ["wq", "wo", "w_down", "attn_out_norm",
                                      "mlp_norm"])
    def test_shared_gradient_is_the_sum_over_the_passes(self, held, batch,
                                                        leaf):
        """The tie of the sharing to the model: an untied, unrolled
        reference of R x L layers (R copies of the stack, pass t over
        copy t) gives each pass's gradient of a weight apart, and the
        program's gradient of the shared weight is their sum."""
        p0 = held["params"]
        untied = jax.grad(
            lambda stacks: ref.loss(p0, *batch, stacks=stacks, **REF_KW)
        )([p0["layers"]] * R)
        for i in range(L):
            per_pass = [np.asarray(untied[t][i][leaf]) for t in range(R)]
            # the passes do differ: no one of them is a third of the sum
            assert np.linalg.norm(per_pass[0] - per_pass[-1]) > (
                0.1 * np.linalg.norm(per_pass[0]))
            want = sum(per_pass)
            got = held["grads"]["layers"][i][leaf]
            assert np.linalg.norm(got - want) < GRAD_RTOL * np.linalg.norm(want)


class TestLayouts:
    def test_remat_on_and_off_agree(self, devices8, held, batch):
        loss, grads, _ = sgd_step(build(devices8, remat=False),
                                  held["params"], *batch)
        assert loss == pytest.approx(held["loss"], rel=1e-6)
        assert worst_rel(grads, held["grads"]) < 1e-5

    def test_chunked_head_agrees(self, devices8, held, batch):
        """The exits through the streamed head (``xent_chunks``): no
        logits kept at all, against the dense head under its remat."""
        loss, grads, counters = sgd_step(build(devices8, xent_chunks=4),
                                         held["params"], *batch)
        assert loss == pytest.approx(held["loss"], rel=1e-6)
        assert worst_rel(grads, held["grads"]) < 1e-5
        np.testing.assert_allclose(counters, held["counters"], rtol=1e-5)

    def test_tp2_is_a_layout_not_other_maths(self, devices8, held, batch):
        """Heads, FFN width and vocabulary sharded over the model
        axis; the four norms and the gate act on the full width."""
        loss, grads, counters = sgd_step(build(devices8, tp=2),
                                         held["params"], *batch)
        assert loss == pytest.approx(held["loss"], rel=1e-6)
        assert worst_rel(grads, held["grads"]) < 1e-5
        np.testing.assert_allclose(counters, held["counters"], rtol=1e-5)

    def test_sp2_agrees(self, devices8, held, batch):
        m = Llama(dict(SMALL, sp=2))
        m.build_model(n_replicas=1)
        m.compile_iter_fns(mesh=make_mesh(seq=2, devices=devices8[:2]))
        loss, grads, _ = sgd_step(m, held["params"], *batch)
        assert loss == pytest.approx(held["loss"], rel=1e-6)
        assert worst_rel(grads, held["grads"]) < 1e-5

    def test_a_looped_moe_counts_a_row_a_layer_call(self, devices8, batch):
        m = build(devices8, n_experts=4, moe_top_k=2, ffn_dim=32)
        p0 = jax.tree.map(np.asarray, jax.device_get(m.params))
        placed = jax.device_put(
            p0, jax.tree.map(lambda a: a.sharding, m.params))
        *_, loss, _, routing, exits = m._train_step(
            placed, m.opt_state, m.ef_state, *m.put_batch(batch),
            jnp.float32(0.1),
        )
        assert np.isfinite(float(loss))
        assert routing.shape == (R * L, 4 + 1)
        assert exits.shape == (2 * R + 1,)


class TestTheExitsHead:
    """The dense head of a looped step computes its gradients in its
    forward pass (``tp.exits_unembed_xent``): the evidence that it
    engaged is static.  Vocabulary 160 and 2 x 16 tokens, so that no
    other side of the model is as wide as either."""

    V = 160

    @pytest.fixture(scope="class")
    def model(self, devices8):
        return build(devices8, vocab=self.V, seq_len=16)

    def wide_products(self, text):
        return [ln for ln in text.splitlines()
                if "dot_general" in ln and f"x{self.V}x" in ln]

    @pytest.mark.parametrize("step, products", [("train", 3), ("val", 1)])
    def test_head_products_in_the_lowered_step(self, model, step, products):
        """Logits, dx and dW in the one loop body over the R exits
        (the mapped dense head under its remat had a fourth, the
        replayed logits); validation reads the last exit alone."""
        batch = model.put_batch((np.zeros((2, 16), np.int32),) * 2)
        if step == "train":
            lowered = model._train_step.lower(
                model.params, model.opt_state, model.ef_state, *batch,
                jnp.float32(LR))
        else:
            lowered = model._val_step.lower(model.params, *batch)
        assert len(self.wide_products(lowered.as_text())) == products

    def test_no_logits_cross_to_the_backward(self, model):
        """Of what the looped loss keeps for its backward pass, the
        only arrays with a vocabulary-wide side are ``lm_head`` and
        its gradient: no ``[N, V]`` logits, of one exit or of R."""
        from jax.sharding import PartitionSpec as P

        def loss(p, x, y):
            exits = model._forward(p, x, head=False)
            out = model._exit_loss(
                p, exits.reshape(R, -1, exits.shape[-1]), y.reshape(-1))
            return jax.lax.pmean(out[0], ("data", "seq"))

        tokens = P("data", "seq")
        x = np.zeros((2, 16), np.int32)
        _, vjp = jax.vjp(
            lambda p: jax.shard_map(
                loss, mesh=model.mesh, in_specs=(model._specs, tokens, tokens),
                out_specs=P())(p, x, x),
            model.params)
        wide = [a.shape for a in jax.tree.leaves(vjp) if self.V in a.shape]
        assert wide == [(SMALL["dim"], self.V)] * 2, wide


class TestDefaultsAreTodaysProgram:
    """``ut_steps`` 1 and the other new knobs at their defaults, on
    the Mistral cell's rehearsal sizes."""

    MISTRAL = dict(
        dim=64, n_layers=2, n_heads=4, n_kv_heads=2, ffn_dim=128, vocab=256,
        seq_len=32, batch_size=2, n_train=8, n_val=1, seed=5,
        compute_dtype="float32", remat=True, optimizer="adam", lr=1e-4,
    )

    def first_step(self, devices, batch, **over):
        m = Llama(dict(self.MISTRAL, **over))
        m.build_model(n_replicas=1)
        m.compile_iter_fns(mesh=make_mesh(model=1, devices=devices[:1]))
        p0 = jax.device_get(m.params)
        out = m._train_step(m.params, m.opt_state, m.ef_state,
                            *m.put_batch(batch), jnp.float32(1e-4))
        return m, p0, out

    @pytest.fixture(scope="class")
    def wide_batch(self):
        rng = np.random.default_rng(1)
        return (rng.integers(0, 256, (2, 32), dtype=np.int32),
                rng.integers(0, 256, (2, 32), dtype=np.int32))

    def test_bit_for_bit(self, devices8, wide_batch):
        _, _, plain = self.first_step(devices8, wide_batch)
        m, p0, stated = self.first_step(
            devices8, wide_batch, ut_steps=1, sandwich_norm=False,
            exit_beta=0.0, rope_theta=10000.0, norm_eps=1e-5)
        assert "exit_gate_w" not in p0 and len(plain) == 5
        for a, b in zip(jax.tree.leaves(plain), jax.tree.leaves(stated)):
            np.testing.assert_array_equal(a, b)
        # and those defaults are the constants of the plain reference
        # of the dense decoder (theta 1e4, eps 1e-5 written into it)
        want = float(plain_ref.loss(p0, *wide_batch, n_heads=4, n_kv_heads=2))
        assert float(plain[3]) == pytest.approx(want, rel=1e-6)

    @pytest.mark.parametrize("cell", ["mistral7b_train_t4096",
                                      "olmoe_train_t4096"])
    def test_one_exit_never_meets_the_exits_head(self, devices8, cell,
                                                 monkeypatch):
        """The separation is by what the code observes, ``ut_steps``:
        the step of the two cells that share ``loss_fn`` and ``tp.py``
        (at their rehearsal sizes) lowers with ``exits_unembed_xent``
        and ``_exit_loss`` made to raise, and its vocabulary-wide
        products are those ``dense_unembed_xent`` lowers to by
        itself."""
        import re

        from benchmark.drivers.train import program_config
        from benchmark.run import load_cell
        from theanompi_tpu.parallel import tp as tp_lib

        config = load_cell(cell)["config"]
        cfg = program_config(dict(config, **config["rehearsal"]),
                             seed=0, n_replicas=1)
        cfg.pop("device_data_cache")

        def refuse(*a, **k):
            raise AssertionError("the exits' head in a step of one exit")

        monkeypatch.setattr(tp_lib, "exits_unembed_xent", refuse)
        monkeypatch.setattr(Llama, "_exit_loss", refuse)
        m = Llama(cfg)
        m.build_model(n_replicas=1)
        m.compile_iter_fns(mesh=make_mesh(model=1, devices=devices8[:1]))
        assert m.ut_steps == 1
        b = np.zeros((cfg["batch_size"], cfg["seq_len"]), np.int32)
        step = m._train_step.lower(
            m.params, m.opt_state, m.ef_state, *m.put_batch((b, b)),
            jnp.float32(1e-4)).as_text()

        def products(text):
            """The vocabulary-wide products, as operand and result
            types with their contracting dimensions."""
            return sorted(
                re.search(r"contracting_dims = \S+ x \S+", ln).group()
                + ln.rsplit(" : ", 1)[1]
                for ln in text.splitlines()
                if "dot_general" in ln and f"x{m.vocab}x" in ln
            )

        n = cfg["batch_size"] * cfg["seq_len"]
        head = jax.jit(jax.grad(
            lambda x, w: jnp.sum(tp_lib.dense_unembed_xent(
                x, w, jnp.zeros((n,), jnp.int32), m.vocab, None)[0]),
            argnums=(0, 1),
        )).lower(jnp.zeros((n, m.dim)), jnp.zeros((m.dim, m.vocab))).as_text()
        assert len(products(head)) == 3
        assert products(step) == products(head)

    @pytest.mark.parametrize("knob", [dict(rope_theta=1e6),
                                      dict(norm_eps=1e-2)], ids=str)
    def test_the_constants_reach_the_step(self, devices8, wide_batch, knob):
        _, _, plain = self.first_step(devices8, wide_batch)
        _, _, moved = self.first_step(devices8, wide_batch, **knob)
        assert abs(float(moved[3]) - float(plain[3])) > 1e-4


class TestExitDistribution:
    def test_q_sums_to_one_a_token(self, held, batch):
        kw = {k: v for k, v in REF_KW.items() if k != "beta"}
        q, xent = ref.sequence_terms(held["params"], batch[0][0],
                                        batch[1][0], **kw)
        assert q.shape == (R, SMALL["seq_len"]) and xent.shape == q.shape
        np.testing.assert_allclose(np.sum(q, 0), 1.0, atol=1e-6)
        assert float(jnp.min(q)) > 0

    def test_step_counters_match_the_reference(self, held, batch):
        kw = {k: v for k, v in REF_KW.items() if k != "beta"}
        q, xent = (np.concatenate(a, 1) for a in zip(*(
            ref.sequence_terms(held["params"], ids, tgt, **kw)
            for ids, tgt in zip(*batch)
        )))
        c = obs.exits.ut_counters(held["counters"])
        assert obs.last_ut_counters() is c
        assert sum(c["ut_exit_mass"]) == pytest.approx(1.0, abs=1e-6)
        np.testing.assert_allclose(c["ut_exit_mass"], q.mean(1), rtol=1e-5)
        np.testing.assert_allclose(c["ut_exit_loss"], xent.mean(1), rtol=1e-5)
        assert c["ut_mean_exit_step"] == pytest.approx(
            float(np.sum(q.mean(1) * np.arange(1, R + 1))), rel=1e-5)

    def test_a_zero_gate_halves_the_mass_at_every_exit(self, devices8, batch):
        m = build(devices8, ut_steps=4)
        p0 = jax.tree.map(np.asarray, jax.device_get(m.params))
        p0["exit_gate_w"] = np.zeros_like(p0["exit_gate_w"])
        _, _, counters = sgd_step(m, p0, *batch)
        c = obs.exits.ut_counters(counters)
        np.testing.assert_allclose(c["ut_exit_mass"],
                                   [0.5, 0.25, 0.125, 0.125], rtol=1e-6)
        assert c["ut_mean_exit_step"] == pytest.approx(1.875, rel=1e-6)


class TestThroughTheRule:
    def test_bsp_trains_it_and_the_summary_has_the_counters(self):
        """``BSP().init``, the worker loop and ``train_chunk`` (two
        steps a dispatch from the device's copy of the data), as every
        other model; Adam memorises the one chunk."""
        rule = theanompi_tpu.BSP()
        rule.init(
            devices=[0], modelfile="theanompi_tpu.models.llama",
            modelclass="Llama", launch="inprocess", verbose=False,
            config=dict(SMALL, optimizer="adam", lr=3e-3, n_epochs=12,
                        n_train=4, device_data_cache=True, steps_per_call=2),
        )
        res = rule.wait()
        losses = res["recorder"].train_losses
        assert res["iterations"] == 24 and losses[-1] < 0.8 * losses[0]
        c = res["ut_counters"]
        assert c is obs.last_ut_counters()
        assert sum(c["ut_exit_mass"]) == pytest.approx(1.0, abs=1e-5)
        assert len(c["ut_exit_loss"]) == R
        assert 1.0 < c["ut_mean_exit_step"] < R
        assert res["moe_counters"] is None
        assert "tm_train_ut_mean_exit_step" in res["recorder"].metrics_txt()
        # one set of layers in what the optimizer and a checkpoint hold
        trees = res["model"].checkpoint_trees()
        assert len(trees["params"]["layers"]) == L
        assert len(trees["opt_state"]["m"]["layers"]) == L

    def test_recorder_reads_the_counters_at_its_fence(self, devices8):
        m = build(devices8)
        rec = Recorder(verbose=False)
        m.train_iter(0, rec)
        assert rec.ut_counters is None      # a device value until the fence
        rec.fence()
        assert len(rec.ut_counters["ut_exit_mass"]) == R

    def test_a_plain_decoder_has_none(self, devices8):
        m = build(devices8, ut_steps=1)
        rec = Recorder(verbose=False)
        m.train_iter(0, rec)
        rec.fence()
        assert rec.ut_counters is None

    def test_validation_reads_the_last_exit(self, devices8, held, batch):
        """``val_iter``'s logits are the last exit's: its loss is that
        exit's plain cross-entropy."""
        m = held["model"]
        m.params = jax.device_put(
            held["params"], jax.tree.map(lambda a: a.sharding, m.params))
        loss, _, _ = m._val_step(m.params, *m.put_batch(batch))
        assert float(loss) == pytest.approx(held["counters"][2 * R - 1],
                                            rel=1e-5)


class TestRefusals:
    def test_pipeline_parallelism_is_refused(self):
        with pytest.raises(NotImplementedError, match="looped decoder"):
            Llama(dict(SMALL, pp=2))

    @pytest.mark.parametrize("over", [dict(), dict(ut_steps=1)],
                             ids=["looped", "sandwich_only"])
    def test_serving_refuses_what_it_would_compute_wrong(self, devices8,
                                                         over):
        """The decoders know neither a cache per (pass, layer) nor the
        branch-output norms: refused, never served without them."""
        with pytest.raises(NotImplementedError, match="not yet servable"):
            build(devices8, **over).make_decoder(max_slots=2, max_seq=32)

    def test_and_listed(self):
        doc = (Path(__file__).resolve().parent.parent / "docs"
               / "REFUSALS.md").read_text()
        assert "a looped decoder (ut_steps > 1)" in doc
        assert "sandwich norms" in doc
