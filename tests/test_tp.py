"""Tensor-parallel primitives (`parallel/tp.py`) vs unsharded numpy math.

New-framework scope — SURVEY §2.2 row "Tensor parallel" (absent
upstream).  Every sharded op is checked against its dense single-device
equivalent on the virtual 8-device CPU mesh.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import PartitionSpec as P

from theanompi_tpu.parallel import MODEL_AXIS, SEQ_AXIS, make_mesh
from theanompi_tpu.parallel import tp as tp_lib


def tp_mesh(devices8, tp=4):
    return make_mesh(data=1, model=tp, devices=devices8[:tp])


def run_tp(mesh, fn, in_specs, out_specs, *args):
    return jax.jit(
        jax.shard_map(
            fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=False,
        )
    )(*args)


class TestShardedMatmuls:
    def test_col_then_row_equals_dense(self, devices8, rng):
        mesh = tp_mesh(devices8)
        x = rng.standard_normal((2, 8, 16)).astype(np.float32)
        w1 = rng.standard_normal((16, 32)).astype(np.float32)
        w2 = rng.standard_normal((32, 16)).astype(np.float32)

        def fn(x, w1, w2):
            h = tp_lib.col_parallel(x, w1)     # [., 32/tp]
            return tp_lib.row_parallel(h, w2)  # [., 16] replicated

        out = run_tp(
            mesh, fn,
            (P(), P(None, MODEL_AXIS), P(MODEL_AXIS, None)), P(),
            x, w1, w2,
        )
        np.testing.assert_allclose(out, (x @ w1) @ w2, rtol=2e-4, atol=2e-4)


class TestVocabSharded:
    VOCAB = 32

    def test_embed_lookup(self, devices8, rng):
        mesh = tp_mesh(devices8)
        table = rng.standard_normal((self.VOCAB, 8)).astype(np.float32)
        ids = rng.integers(0, self.VOCAB, (2, 16)).astype(np.int32)

        out = run_tp(
            mesh,
            lambda i, t: tp_lib.embed_lookup(i, t, self.VOCAB),
            (P(), P(MODEL_AXIS, None)), P(),
            ids, table,
        )
        np.testing.assert_allclose(out, table[ids], rtol=1e-6)

    def test_sharded_xent_matches_dense(self, devices8, rng):
        mesh = tp_mesh(devices8)
        logits = rng.standard_normal((4, 6, self.VOCAB)).astype(np.float32)
        labels = rng.integers(0, self.VOCAB, (4, 6)).astype(np.int32)

        loss = run_tp(
            mesh,
            lambda lg, lb: tp_lib.sharded_softmax_xent(lg, lb, self.VOCAB),
            (P(None, None, MODEL_AXIS), P()), P(),
            logits, labels,
        )
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        tgt = np.take_along_axis(logits, labels[..., None], -1)[..., 0]
        np.testing.assert_allclose(loss, np.mean(lse - tgt), rtol=1e-5)

    def test_sharded_top1_and_topk(self, devices8, rng):
        mesh = tp_mesh(devices8)
        logits = rng.standard_normal((4, 6, self.VOCAB)).astype(np.float32)
        labels = rng.integers(0, self.VOCAB, (4, 6)).astype(np.int32)

        err1, err5 = run_tp(
            mesh,
            lambda lg, lb: (
                tp_lib.sharded_top1_err(lg, lb, self.VOCAB),
                tp_lib.sharded_topk_err(lg, lb, self.VOCAB, k=5),
            ),
            (P(None, None, MODEL_AXIS), P()), (P(), P()),
            logits, labels,
        )
        want1 = np.mean(np.argmax(logits, -1) != labels)
        top5 = np.argsort(-logits, -1)[..., :5]
        want5 = 1.0 - np.mean(np.any(top5 == labels[..., None], -1))
        np.testing.assert_allclose(err1, want1, rtol=1e-6)
        np.testing.assert_allclose(err5, want5, rtol=1e-6)


class TestGradSync:
    def test_replicated_leaf_averaged_sharded_leaf_untouched(
        self, devices8
    ):
        mesh = make_mesh(data=2, model=2, devices=devices8[:4])
        specs = {"norm": P(None), "wq": P(None, MODEL_AXIS)}

        def fn():
            r = lax.axis_index("data").astype(jnp.float32)
            grads = {
                "norm": jnp.full((4,), r),        # differs across data
                "wq": jnp.ones((2, 2)),
            }
            return tp_lib.grad_sync(grads, specs)

        out = jax.jit(
            jax.shard_map(
                fn, mesh=mesh, in_specs=(),
                out_specs={"norm": P(None), "wq": P(None, MODEL_AXIS)},
                check_vma=False,
            )
        )()
        # data ranks held 0 and 1 -> mean 0.5 everywhere
        np.testing.assert_allclose(out["norm"], 0.5)
        np.testing.assert_allclose(out["wq"], 1.0)


class TestCustomHeads:
    """The hand-written head VJPs anchored against AUTODIFF of the
    plain dense math (r4 code-review find: comparing the two manual
    VJPs only to each other would let a shared bug hide)."""

    def _data(self, rng, n=24, d=16, v=64):
        x = jnp.asarray(rng.standard_normal((n, d)).astype(np.float32))
        w = jnp.asarray(rng.standard_normal((d, v)).astype(np.float32))
        y = jnp.asarray(rng.integers(0, v, n).astype(np.int32))
        return x, w, y, v

    @staticmethod
    def _autodiff_ref(x, w, y):
        lg = (x @ w).astype(jnp.float32)
        lse = jax.scipy.special.logsumexp(lg, axis=-1)
        tgt = jnp.take_along_axis(lg, y[:, None], axis=-1)[:, 0]
        return jnp.mean(lse - tgt), jnp.argmax(lg, -1)

    @pytest.mark.parametrize("head", ["dense", "chunked"])
    def test_value_pred_and_grads_match_autodiff(self, rng, head):
        x, w, y, v = self._data(rng)

        def custom(x, w):
            if head == "dense":
                lv, pred = tp_lib.dense_unembed_xent(x, w, y, v, None)
            else:
                lv, pred = tp_lib.chunked_unembed_xent(
                    x, w, y, v, 4, None
                )
            return jnp.mean(lv), pred

        (l_c, p_c) = custom(x, w)
        (l_r, p_r) = self._autodiff_ref(x, w, y)
        np.testing.assert_allclose(float(l_c), float(l_r), rtol=1e-6)
        np.testing.assert_array_equal(np.asarray(p_c), np.asarray(p_r))
        g_c = jax.grad(lambda x, w: custom(x, w)[0], argnums=(0, 1))(x, w)
        g_r = jax.grad(
            lambda x, w: self._autodiff_ref(x, w, y)[0], argnums=(0, 1)
        )(x, w)
        for name, a, b in zip(("dx", "dw"), g_c, g_r):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=2e-7,
                err_msg=f"{head} {name}",
            )


class TestExitsHead:
    """``exits_unembed_xent`` (the R exits of a looped decoder through
    one dense head that computes its gradients in its forward pass)
    against what it replaced: a ``lax.map`` over ``dense_unembed_xent``
    under its remat, the row weights reaching it as the cotangent of
    its loss vector.  Under the step's own vma-checked ``shard_map``."""

    R, N, D, V = 3, 24, 16, 64
    G = 0.37        # a scalar cotangent other than 1
    SPECS = (P(None, SEQ_AXIS), P(None, MODEL_AXIS), P(SEQ_AXIS),
             P(None, SEQ_AXIS))        # xs, w, labels, row_w

    @pytest.fixture
    def data(self, rng):
        return (
            rng.standard_normal((self.R, self.N, self.D)).astype(np.float32),
            rng.standard_normal((self.D, self.V)).astype(np.float32),
            rng.integers(0, self.V, self.N).astype(np.int32),
            rng.uniform(0.1, 1.0, (self.R, self.N)).astype(np.float32),
        )

    def exits_head(self, xs, w, y, row_w, axis=MODEL_AXIS):
        return tp_lib.exits_unembed_xent(xs, w, y, row_w, self.V, axis)

    def mapped_dense_head(self, xs, w, y, row_w, axis=MODEL_AXIS,
                          remat=True):
        def head(z):
            return tp_lib.dense_unembed_xent(z, w, y, self.V, axis)

        xent, pred = lax.map(jax.checkpoint(head) if remat else head, xs)
        return jnp.sum(row_w * xent), xent, pred

    def run(self, head, mesh, data):
        """(G * total over all tokens, xent, pred), and the gradients
        of the first to xs, w and row_w."""
        def fn(xs, w, y, row_w):
            def loss(xs, w, row_w):
                total, xent, pred = head(xs, w, y, row_w)
                return self.G * lax.psum(total, SEQ_AXIS), (xent, pred)

            (total, aux), grads = jax.value_and_grad(
                loss, argnums=(0, 1, 2), has_aux=True)(xs, w, row_w)
            return (total, *aux), grads

        xs, w, _, row_w = self.SPECS
        return jax.jit(jax.shard_map(
            fn, mesh=mesh, in_specs=self.SPECS,
            out_specs=((P(), row_w, row_w), (xs, w, row_w)),
        ))(*data)

    @pytest.mark.parametrize("layout", [dict(), dict(model=2), dict(seq=2)],
                             ids=["one_device", "tp2", "sp2"])
    def test_matches_the_mapped_dense_head(self, devices8, data, layout):
        want_out, want_grads = self.run(
            self.mapped_dense_head, make_mesh(devices=devices8[:1]), data)
        got_out, got_grads = self.run(
            self.exits_head,
            make_mesh(devices=devices8[:2 if layout else 1], **layout), data)
        np.testing.assert_allclose(got_out[0], want_out[0], rtol=1e-6)
        np.testing.assert_allclose(got_out[1], want_out[1], rtol=1e-5)
        np.testing.assert_array_equal(got_out[2], want_out[2])
        for name, a, b in zip(("dxs", "dw", "drow_w"), got_grads, want_grads):
            assert float(jnp.max(jnp.abs(b))) > 1e-3, name
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6,
                                       err_msg=name)

    @pytest.mark.parametrize("layout", [dict(), dict(model=2)],
                             ids=["one_device", "tp2"])
    def test_a_row_of_labels_an_exit_equals_a_dense_head_an_exit(
        self, devices8, data, rng, layout
    ):
        """``labels [R, N]`` (a multi-token-prediction module's exit is
        held to another token than the main one): total, xent, pred
        and all three gradients equal R calls of the dense head, each
        on its own labels."""
        xs, w, _, row_w = data
        labels = rng.integers(0, self.V, (self.R, self.N)).astype(np.int32)
        specs = (self.SPECS[0], self.SPECS[1], P(None, SEQ_AXIS),
                 self.SPECS[3])

        def dense_heads(xs, w, ys, row_w, axis=MODEL_AXIS):
            xent, pred = zip(*(
                tp_lib.dense_unembed_xent(xs[r], w, ys[r], self.V, axis)
                for r in range(self.R)))
            xent, pred = jnp.stack(xent), jnp.stack(pred)
            return jnp.sum(row_w * xent), xent, pred

        def run(head, mesh):
            def fn(xs, w, ys, row_w):
                def loss(xs, w, row_w):
                    total, xent, pred = head(xs, w, ys, row_w)
                    return self.G * lax.psum(total, SEQ_AXIS), (xent, pred)

                (total, aux), grads = jax.value_and_grad(
                    loss, argnums=(0, 1, 2), has_aux=True)(xs, w, row_w)
                return (total, *aux), grads

            return jax.jit(jax.shard_map(
                fn, mesh=mesh, in_specs=specs,
                out_specs=((P(), specs[3], specs[3]),
                           (specs[0], specs[1], specs[3])),
            ))(xs, w, labels, row_w)

        want_out, want_grads = run(dense_heads, make_mesh(devices=devices8[:1]))
        got_out, got_grads = run(
            self.exits_head,
            make_mesh(devices=devices8[:2 if layout else 1], **layout))
        np.testing.assert_allclose(got_out[0], want_out[0], rtol=1e-6)
        np.testing.assert_allclose(got_out[1], want_out[1], rtol=1e-5)
        np.testing.assert_array_equal(got_out[2], want_out[2])
        for name, a, b in zip(("dxs", "dw", "drow_w"), got_grads, want_grads):
            assert float(jnp.max(jnp.abs(b))) > 1e-3, name
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6,
                                       err_msg=name)
        # one row of labels for all exits is the [N] form
        same = np.tile(labels[:1], (self.R, 1))
        a = self.exits_head(xs, w, same, row_w, axis=None)
        b = self.exits_head(xs, w, labels[0], row_w, axis=None)
        for x_, y_ in zip(a, b):
            np.testing.assert_array_equal(x_, y_)

    def residual_shapes(self, head, data):
        xs, w, y, row_w = data
        _, vjp = jax.vjp(
            lambda xs, w, row_w: head(xs, w, y, row_w, axis=None)[0],
            xs, w, row_w)
        return [a.shape for a in jax.tree.leaves(vjp)]

    def test_no_logits_cross_to_the_backward(self, data):
        """What the forward keeps for the backward holds no [N, V]
        array (the mapped dense head WITHOUT its remat keeps R)."""
        logits = {(self.N, self.V), (self.R, self.N, self.V)}
        kept = self.residual_shapes(self.exits_head, data)
        assert not logits & set(kept), kept
        assert (self.R, self.N, self.D) in kept and (self.D, self.V) in kept
        assert (self.R, self.N, self.V) in self.residual_shapes(
            functools.partial(self.mapped_dense_head, remat=False), data)

    @pytest.mark.parametrize("differentiated, products", [(True, 3),
                                                          (False, 1)])
    def test_products_in_the_lowered_text(self, data, differentiated,
                                          products):
        """One loop body over the R exits: logits, dx and dW under
        ``grad`` (the mapped dense head's two bodies held four), the
        logits alone in an undifferentiated call (validation)."""
        xs, w, y, row_w = data

        def total(xs, w, row_w):
            return self.exits_head(xs, w, y, row_w, axis=None)[0]

        fn = jax.grad(total, argnums=(0, 1, 2)) if differentiated else total
        text = jax.jit(fn).lower(xs, w, row_w).as_text()
        wide = [ln for ln in text.splitlines()
                if "dot_general" in ln and f"x{self.V}x" in ln]
        assert len(wide) == products, wide
        assert text.count("stablehlo.while") == 1
