"""Unit tests for the exchange-rule math against numpy (SURVEY §4b)."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P
from jax import shard_map

from theanompi_tpu.parallel import (
    DATA_AXIS,
    EXPERT_AXIS,
    allreduce_mean,
    elastic_pair_update,
    flat_pack,
    flat_pack_bucket,
    flat_spec,
    flat_spec_cache_clear,
    flat_spec_cache_info,
    flat_unpack,
    get_strategy,
    gossip_merge,
    gossip_push,
    make_mesh,
    scatter_update_gather,
)
from theanompi_tpu.parallel.exchange import (
    exchange_bucket_count,
    flat_layout,
)
from theanompi_tpu.parallel.strategies import STRATEGIES
from theanompi_tpu.parallel.exchange import (
    elastic_center_merge,
    replica_consistency_delta,
)
from theanompi_tpu.ops import optimizers as opt_lib


def _tree(rng, scale=1.0):
    return {
        "w": jnp.asarray(rng.normal(size=(8, 4, 3)) * scale, jnp.float32),
        "b": jnp.asarray(rng.normal(size=(5,)) * scale, jnp.float32),
    }


def _per_device_trees(rng, n=8):
    """n distinct pytrees, stacked on a leading device axis."""
    trees = [_tree(rng) for _ in range(n)]
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees), trees


class TestAllreduce:
    @pytest.mark.parametrize("strategy", ["ar", "asa32", "asa16", "nccl32", "nccl16"])
    def test_strategies_mean(self, mesh8, rng, strategy):
        stacked, trees = _per_device_trees(rng)
        strat = get_strategy(strategy)

        fn = shard_map(
            lambda t: jax.tree.map(
                lambda x: x[None],
                strat(jax.tree.map(lambda x: x[0], t), DATA_AXIS),
            ),
            mesh=mesh8,
            in_specs=P(DATA_AXIS),
            out_specs=P(DATA_AXIS),
        )
        # out has a size-1 leading axis per device -> gathered to [8, ...]
        out = jax.jit(fn)(stacked)

        want = jax.tree.map(lambda *xs: np.mean(xs, axis=0), *trees)
        tol = 2e-2 if strategy.endswith("16") else 1e-5
        for k in ("w", "b"):
            got0 = np.asarray(out[k][0])
            gotlast = np.asarray(out[k][-1])
            np.testing.assert_allclose(got0, want[k], rtol=tol, atol=tol)
            # every replica must hold the identical mean
            np.testing.assert_array_equal(got0, gotlast)

    def test_wire_dtype_preserves_param_dtype(self, mesh8, rng):
        stacked, _ = _per_device_trees(rng)
        fn = shard_map(
            lambda t: jax.tree.map(
                lambda x: x[None],
                allreduce_mean(
                    jax.tree.map(lambda x: x[0], t),
                    DATA_AXIS,
                    wire_dtype=jnp.bfloat16,
                ),
            ),
            mesh=mesh8,
            in_specs=P(DATA_AXIS),
            out_specs=P(DATA_AXIS),
        )
        out = jax.jit(fn)(stacked)
        assert out["w"].dtype == jnp.float32

    def test_two_phase_matches_psum(self, mesh8, rng):
        stacked, _ = _per_device_trees(rng)
        def run(two_phase):
            fn = shard_map(
                lambda t: jax.tree.map(
                    lambda x: x[None],
                    allreduce_mean(
                        jax.tree.map(lambda x: x[0], t),
                        DATA_AXIS,
                        two_phase=two_phase,
                    ),
                ),
                mesh=mesh8,
                in_specs=P(DATA_AXIS),
                out_specs=P(DATA_AXIS),
            )
            return jax.jit(fn)(stacked)
        a, b = run(False), run(True)
        np.testing.assert_allclose(np.asarray(a["w"]), np.asarray(b["w"]), rtol=1e-6)


class TestEASGD:
    def test_elastic_pair_math(self, rng):
        local = _tree(rng)
        center = _tree(rng)
        alpha = 0.25
        new_l, new_c = jax.jit(lambda l, c: elastic_pair_update(l, c, alpha))(
            local, center
        )
        for k in local:
            diff = alpha * (np.asarray(local[k]) - np.asarray(center[k]))
            np.testing.assert_allclose(np.asarray(new_l[k]),
                                       np.asarray(local[k]) - diff, rtol=1e-6)
            np.testing.assert_allclose(np.asarray(new_c[k]),
                                       np.asarray(center[k]) + diff, rtol=1e-6)

    def test_elastic_fixed_point(self, rng):
        """When local == center the exchange is a no-op."""
        t = _tree(rng)
        new_l, new_c = elastic_pair_update(t, t, 0.5)
        for k in t:
            np.testing.assert_array_equal(np.asarray(new_l[k]), np.asarray(t[k]))
            np.testing.assert_array_equal(np.asarray(new_c[k]), np.asarray(t[k]))

    def test_center_merge_sums_pushes(self, rng):
        stacked, trees = _per_device_trees(rng, n=4)
        center = _tree(rng)
        alpha = 0.1
        new_w, new_c = jax.jit(
            lambda w, c: elastic_center_merge(w, c, alpha)
        )(stacked, center)
        for k in center:
            pushes = sum(
                alpha * (np.asarray(t[k]) - np.asarray(center[k])) for t in trees
            )
            np.testing.assert_allclose(
                np.asarray(new_c[k]), np.asarray(center[k]) + pushes, rtol=1e-5
            )
            np.testing.assert_allclose(
                np.asarray(new_w[k][2]),
                np.asarray(trees[2][k])
                - alpha * (np.asarray(trees[2][k]) - np.asarray(center[k])),
                rtol=1e-5,
            )


class TestGoSGD:
    def test_merge_math(self, rng):
        a, b = _tree(rng), _tree(rng)
        sa, sb = jnp.float32(0.5), jnp.float32(0.25)
        merged, total = gossip_merge(a, sa, b, sb)
        assert float(total) == pytest.approx(0.75)
        for k in a:
            want = (0.5 * np.asarray(a[k]) + 0.25 * np.asarray(b[k])) / 0.75
            np.testing.assert_allclose(np.asarray(merged[k]), want, rtol=1e-6)

    def test_gossip_push_round(self, mesh8, rng):
        n = 8
        stacked, trees = _per_device_trees(rng, n)
        scores = jnp.ones((n, 1), jnp.float32)  # [device, 1] scalar score each
        # ring permutation: i -> i+1; devices 0 and 3 push
        perm = [(i, (i + 1) % n) for i in range(n)]
        pushing = jnp.zeros((n,), jnp.float32).at[0].set(1).at[3].set(1)

        def step(params, score):
            p = jax.tree.map(lambda x: x[0], params)
            merged, total = gossip_push(
                p, score[0], axis_name=DATA_AXIS, perm=perm, pushing=pushing
            )
            return (
                jax.tree.map(lambda x: x[None], merged),
                total[None],
            )

        fn = shard_map(
            step, mesh=mesh8,
            in_specs=(P(DATA_AXIS), P(DATA_AXIS)),
            out_specs=(P(DATA_AXIS), P(DATA_AXIS)),
        )
        merged, totals = jax.jit(fn)(stacked, scores)
        totals = np.asarray(totals).ravel()

        # pusher 0: kept 0.5, received nothing (7 didn't push) -> 0.5
        assert totals[0] == pytest.approx(0.5)
        # receiver 1: own 1.0 + 0.5 from 0 -> 1.5, params merged 2:1
        assert totals[1] == pytest.approx(1.5)
        want1 = (1.0 * np.asarray(trees[1]["w"]) + 0.5 * np.asarray(trees[0]["w"])) / 1.5
        np.testing.assert_allclose(np.asarray(merged["w"][1]), want1, rtol=1e-5)
        # bystander 5: unchanged params, score 1.0
        assert totals[5] == pytest.approx(1.0)
        np.testing.assert_allclose(
            np.asarray(merged["w"][5]), np.asarray(trees[5]["w"]), rtol=1e-6
        )
        # score mass is conserved
        assert totals.sum() == pytest.approx(n)

    def test_no_push_is_identity(self, mesh8, rng):
        n = 8
        stacked, trees = _per_device_trees(rng, n)
        scores = jnp.ones((n, 1), jnp.float32)
        perm = [(i, (i + 1) % n) for i in range(n)]
        pushing = jnp.zeros((n,), jnp.float32)

        def step(params, score):
            p = jax.tree.map(lambda x: x[0], params)
            merged, total = gossip_push(
                p, score[0], axis_name=DATA_AXIS, perm=perm, pushing=pushing
            )
            return jax.tree.map(lambda x: x[None], merged), total[None]

        fn = shard_map(step, mesh=mesh8,
                       in_specs=(P(DATA_AXIS), P(DATA_AXIS)),
                       out_specs=(P(DATA_AXIS), P(DATA_AXIS)))
        merged, totals = jax.jit(fn)(stacked, scores)
        np.testing.assert_allclose(np.asarray(merged["w"]),
                                   np.asarray(stacked["w"]), rtol=1e-6)
        np.testing.assert_allclose(np.asarray(totals).ravel(), np.ones(n))


class TestZero1Primitive:
    """ZeRO-1 exchange (exchange.scatter_update_gather): reduce-scatter
    grads over the data axis, optimizer update on the 1/N flat shard,
    all-gather updated params — must reproduce allreduce-mean + full
    replicated update exactly."""

    def test_flat_pack_roundtrip_uneven_leaves(self, rng):
        """22 elements over 8 shards: pad-and-concat must round-trip
        shapes, values, and dtypes (bf16 leaf included)."""
        tree = {
            "w": jnp.asarray(rng.normal(size=(5, 3)), jnp.float32),
            "b": jnp.asarray(rng.normal(size=(7,)), jnp.bfloat16),
            "s": jnp.float32(rng.normal()),           # scalar leaf
        }
        spec = flat_spec(tree, 8)
        assert spec.size == 23
        assert spec.padded == 24 and spec.shard_len == 3
        assert spec.dtype == jnp.float32              # mixed -> fp32
        back = flat_unpack(flat_pack(tree, spec), spec)
        for k in tree:
            assert back[k].dtype == tree[k].dtype
            np.testing.assert_allclose(
                np.asarray(back[k], np.float32),
                np.asarray(tree[k], np.float32),
                rtol=1e-2 if tree[k].dtype == jnp.bfloat16 else 0,
            )

    def test_zero1_strategies_registered(self):
        for name in ("zero1", "zero1_16"):
            s = get_strategy(name)
            assert s.zero1 and s.two_phase
        assert not get_strategy("asa32").zero1
        # calling a zero1 strategy directly still allreduce-means
        # (aux exchanges like BN-stat sync route through unchanged)
        fn = shard_map(
            lambda v: get_strategy("zero1")(
                {"x": v[0]}, DATA_AXIS
            )["x"][None],
            mesh=make_mesh(data=8), in_specs=P(DATA_AXIS),
            out_specs=P(DATA_AXIS),
        )
        out = jax.jit(fn)(jnp.arange(8.0)[:, None])
        np.testing.assert_allclose(np.asarray(out), 3.5)

    @pytest.mark.parametrize("opt_name", ["momentum", "adam"])
    def test_matches_allreduce_update(self, mesh8, rng, opt_name):
        opt = opt_lib.get(opt_name)
        tree = {
            "w": jnp.asarray(rng.normal(size=(5, 3)), jnp.float32),
            "b": jnp.asarray(rng.normal(size=(7,)), jnp.float32),
        }
        gstack = jnp.asarray(rng.normal(size=(8, 22)), jnp.float32)
        spec = flat_spec(tree, 8)

        def tree_of(flat):
            return {"w": flat[:15].reshape(5, 3), "b": flat[15:22]}

        def z1(params, ostate, g, lr):
            grads = tree_of(g[0])

            def upd(p_s, g_s):
                return opt.update(p_s, g_s, ostate, lr)

            return scatter_update_gather(
                params, grads, upd, DATA_AXIS, spec=spec
            )

        ostate0 = opt.shard_state(spec.shard_len)
        osp = jax.tree.map(
            lambda x: P(DATA_AXIS) if jnp.ndim(x) else P(), ostate0
        )
        step = jax.jit(shard_map(
            z1, mesh=mesh8,
            in_specs=(P(), osp, P(DATA_AXIS), P()),
            out_specs=(P(), osp),
        ))
        ostate_g = jax.tree.map(
            lambda x: jnp.zeros((spec.padded,), x.dtype)
            if jnp.ndim(x) else x,
            ostate0,
        )
        p1, o1 = step(tree, ostate_g, gstack, jnp.float32(0.1))

        def ref(params, ostate, g, lr):
            grads = allreduce_mean(tree_of(g[0]), DATA_AXIS)
            return opt.update(params, grads, ostate, lr)

        rstep = jax.jit(shard_map(
            ref, mesh=mesh8,
            in_specs=(P(), P(), P(DATA_AXIS), P()),
            out_specs=(P(), P()),
        ))
        p2, _ = rstep(tree, opt.init(tree), gstack, jnp.float32(0.1))
        for k in tree:
            np.testing.assert_allclose(
                np.asarray(p1[k]), np.asarray(p2[k]),
                rtol=2e-6, atol=2e-7,
            )

    def test_tuple_axes_scatter(self, devices8, rng):
        """(expert, data) joint scatter: the flat shard index must
        follow the collective's tiling order, or params come back
        permuted — equivalence against allreduce over the same tuple
        pins it."""
        mesh = make_mesh(expert=2, data=4, devices=devices8)
        axes = (EXPERT_AXIS, DATA_AXIS)
        tree = {"w": jnp.asarray(rng.normal(size=(3, 3)), jnp.float32)}
        gstack = jnp.asarray(rng.normal(size=(8, 9)), jnp.float32)
        opt = opt_lib.sgd()

        def z1(params, g, lr):
            grads = {"w": g[0].reshape(3, 3)}

            def upd(p_s, g_s):
                return opt.update(p_s, g_s, (), lr)

            new_p, _ = scatter_update_gather(params, grads, upd, axes)
            return new_p

        step = jax.jit(shard_map(
            z1, mesh=mesh,
            in_specs=(P(), P((EXPERT_AXIS, DATA_AXIS)), P()),
            out_specs=P(),
        ))
        p1 = step(tree, gstack, jnp.float32(0.5))
        want = np.asarray(tree["w"]) - 0.5 * np.mean(
            np.asarray(gstack), axis=0
        ).reshape(3, 3)
        np.testing.assert_allclose(
            np.asarray(p1["w"]), want, rtol=2e-6, atol=2e-7
        )


class TestFlatPackEdges:
    """flat_pack/flat_unpack edge cases + bucket-boundary layouts
    (ISSUE 2 satellite)."""

    def test_zero_size_leaf_roundtrip(self, rng):
        tree = {
            "w": jnp.asarray(rng.normal(size=(4, 3)), jnp.float32),
            "empty": jnp.zeros((0,), jnp.float32),
            "e2": jnp.zeros((3, 0, 2), jnp.float32),
        }
        spec = flat_spec(tree, 8)
        assert spec.size == 12
        back = flat_unpack(flat_pack(tree, spec), spec)
        for k in tree:
            assert back[k].shape == tree[k].shape
            np.testing.assert_array_equal(
                np.asarray(back[k]), np.asarray(tree[k])
            )

    def test_fewer_leaves_than_shards(self, rng):
        """2 leaves over 8 shards: padding must still shard evenly and
        round-trip."""
        tree = {
            "a": jnp.asarray(rng.normal(size=(3,)), jnp.float32),
            "b": jnp.float32(1.5),
        }
        spec = flat_spec(tree, 8)
        assert spec.size == 4 and spec.padded == 8
        assert spec.shard_len == 1
        back = flat_unpack(flat_pack(tree, spec), spec)
        np.testing.assert_array_equal(np.asarray(back["a"]),
                                      np.asarray(tree["a"]))
        assert float(back["b"]) == 1.5

    def test_mixed_dtype_roundtrip(self, rng):
        tree = {
            "f32": jnp.asarray(rng.normal(size=(5,)), jnp.float32),
            "bf16": jnp.asarray(rng.normal(size=(6,)), jnp.bfloat16),
            "i32": jnp.arange(7, dtype=jnp.int32),
        }
        spec = flat_spec(tree, 4)
        assert spec.dtype == jnp.float32          # mixed -> master fp32
        back = flat_unpack(flat_pack(tree, spec), spec)
        for k in tree:
            assert back[k].dtype == tree[k].dtype
            np.testing.assert_allclose(
                np.asarray(back[k], np.float32),
                np.asarray(tree[k], np.float32),
                rtol=1e-2 if tree[k].dtype == jnp.bfloat16 else 0,
            )

    def test_pad_length_roundtrip_identity(self, rng):
        """padded > size: the pad is dropped exactly, values identical."""
        tree = {"w": jnp.asarray(rng.normal(size=(13,)), jnp.float32)}
        spec = flat_spec(tree, 8)
        assert spec.padded == 16 and spec.size == 13
        buf = flat_pack(tree, spec)
        assert buf.shape == (16,)
        np.testing.assert_array_equal(np.asarray(buf[13:]), 0.0)
        np.testing.assert_array_equal(
            np.asarray(flat_unpack(buf, spec)["w"]),
            np.asarray(tree["w"]),
        )

    def test_bucket_not_dividing_buffer(self, rng):
        """bucket size not dividing the (mono-padded) buffer: padded
        rounds up to a whole bucket count; concat of buckets equals
        the monolithic pack on the live prefix."""
        tree = {"w": jnp.asarray(rng.normal(size=(50,)), jnp.float32)}
        # 8 shards: mono padded 56; bucket_elems 20 -> bucket_len 24,
        # padded 72, 3 buckets
        spec = flat_spec(tree, 8, bucket_elems=20)
        assert (spec.bucket_len, spec.padded, spec.n_buckets) == (24, 72, 3)
        assert spec.bucket_shard_len == 3
        parts = jnp.concatenate([
            flat_pack_bucket(tree, spec, i) for i in range(spec.n_buckets)
        ])
        np.testing.assert_array_equal(
            np.asarray(parts), np.asarray(flat_pack(tree, spec))
        )
        np.testing.assert_array_equal(
            np.asarray(flat_unpack(parts, spec)["w"]),
            np.asarray(tree["w"]),
        )

    def test_bucket_count_cap(self):
        """The unrolled pipeline's HLO size is linear in bucket
        count, so flat_layout caps it by growing the bucket size —
        a flagship-scale pack at a tiny bucket target must not
        unroll thousands of bodies."""
        from theanompi_tpu.parallel.exchange import MAX_EXCHANGE_BUCKETS

        padded, bl = flat_layout(10_000_000, 8, 1000)
        assert bl > 0
        assert padded // bl <= MAX_EXCHANGE_BUCKETS
        # uncapped requests keep their size
        padded, bl = flat_layout(10_000_000, 8, 4 * 2**20 // 4)
        assert bl == 4 * 2**20 // 4
        assert padded // bl <= MAX_EXCHANGE_BUCKETS

    def test_resolve_bucket_mb(self):
        from theanompi_tpu.parallel import (
            DEFAULT_BUCKET_MB,
            resolve_bucket_mb,
        )

        assert resolve_bucket_mb(None) == DEFAULT_BUCKET_MB
        assert resolve_bucket_mb({}) == DEFAULT_BUCKET_MB
        assert resolve_bucket_mb({"exchange_bucket_mb": 0}) == 0.0
        assert resolve_bucket_mb({"exchange_bucket_mb": None}) == 0.0
        assert resolve_bucket_mb({"exchange_bucket_mb": 0.25}) == 0.25
        with pytest.raises(ValueError, match="exchange_bucket_mb"):
            resolve_bucket_mb({"exchange_bucket_mb": -1})

    def test_bucket_larger_than_buffer_degrades_to_monolithic(self, rng):
        tree = {"w": jnp.asarray(rng.normal(size=(50,)), jnp.float32)}
        spec = flat_spec(tree, 8, bucket_elems=1000)
        assert spec.bucket_len == 0 and spec.n_buckets == 1
        assert spec.padded == 56                 # the monolithic layout
        # and the degraded spec is the SAME layout flat_layout computes
        assert flat_layout(50, 8, 1000) == (56, 0)
        assert flat_layout(50, 8, 0) == (56, 0)
        assert flat_layout(50, 8, 20) == (72, 24)

    def test_bucket_pack_covers_leaf_boundaries(self, rng):
        """Leaves spanning bucket boundaries and buckets fully inside
        one leaf both pack correctly (mixed dtypes + a zero-size
        leaf riding along)."""
        tree = {
            "a": jnp.asarray(rng.normal(size=(30,)), jnp.float32),
            "z": jnp.zeros((0,), jnp.float32),
            "b": jnp.asarray(rng.normal(size=(3, 3)), jnp.bfloat16),
            "c": jnp.asarray(rng.normal(size=(25,)), jnp.float32),
        }
        spec = flat_spec(tree, 4, bucket_elems=8)
        assert spec.n_buckets == spec.padded // spec.bucket_len > 1
        parts = jnp.concatenate([
            flat_pack_bucket(tree, spec, i) for i in range(spec.n_buckets)
        ])
        np.testing.assert_array_equal(
            np.asarray(parts), np.asarray(flat_pack(tree, spec))
        )


class TestFlatSpecCache:
    """flat_spec memoization (ISSUE 2 satellite): same layout hits,
    distinct shard counts / dtypes / bucket sizes miss."""

    def test_hits_and_misses(self, rng):
        flat_spec_cache_clear()
        tree = {"w": jnp.asarray(rng.normal(size=(8, 4)), jnp.float32),
                "b": jnp.asarray(rng.normal(size=(5,)), jnp.float32)}
        s1 = flat_spec(tree, 8)
        assert flat_spec_cache_info() == {
            "hits": 0, "misses": 1, "size": 1}
        s2 = flat_spec(tree, 8)
        assert s2 is s1                           # memoized object
        assert flat_spec_cache_info()["hits"] == 1
        # same structure, fresh arrays: still a hit (keyed on layout)
        tree2 = jax.tree.map(lambda x: x + 1, tree)
        assert flat_spec(tree2, 8) is s1
        assert flat_spec_cache_info()["hits"] == 2
        # distinct shard count, bucket size, dtype, leaf dtype: miss
        assert flat_spec(tree, 4) is not s1
        assert flat_spec(tree, 8, bucket_elems=16) is not s1
        assert flat_spec(tree, 8, dtype=jnp.bfloat16) is not s1
        tree_bf = jax.tree.map(
            lambda x: x.astype(jnp.bfloat16), tree
        )
        assert flat_spec(tree_bf, 8) is not s1
        info = flat_spec_cache_info()
        assert info["misses"] == 5 and info["hits"] == 2

    def test_distinct_shapes_miss(self, rng):
        flat_spec_cache_clear()
        a = {"w": jnp.zeros((8,), jnp.float32)}
        b = {"w": jnp.zeros((9,), jnp.float32)}
        assert flat_spec(a, 4) is not flat_spec(b, 4)
        assert flat_spec_cache_info()["misses"] == 2


class TestBucketedExchange:
    """Bucketed overlap-scheduled exchange (ISSUE 2 tentpole): the
    bucketed pipeline must be bitwise-equal to the monolithic path —
    bucketing only changes the dependence structure XLA schedules,
    never the math."""

    TREE_SHAPES = {"w": (37, 5), "b": (11,)}

    def _tree(self, rng):
        return {k: jnp.asarray(rng.normal(size=s), jnp.float32)
                for k, s in self.TREE_SHAPES.items()}

    def _tree_of(self, flat):
        return {"w": flat[:185].reshape(37, 5), "b": flat[185:196]}

    @pytest.mark.parametrize("opt_name", ["momentum", "adam", "sgd"])
    def test_bucketed_zero1_matches_monolithic(self, mesh8, rng, opt_name):
        opt = opt_lib.get(opt_name)
        tree = self._tree(rng)
        gstack = jnp.asarray(rng.normal(size=(8, 196)), jnp.float32)

        def run(spec):
            st0 = opt.shard_state(spec.shard_len)

            def z1(params, ostate, g, lr):
                def upd(p_s, g_s, st):
                    return opt.update(p_s, g_s, st, lr)

                return scatter_update_gather(
                    params, self._tree_of(g[0]), upd, DATA_AXIS,
                    spec=spec, opt_state=ostate,
                )

            osp = jax.tree.map(
                lambda x: P(DATA_AXIS) if jnp.ndim(x) else P(), st0
            )
            step = jax.jit(shard_map(
                z1, mesh=mesh8,
                in_specs=(P(), osp, P(DATA_AXIS), P()),
                out_specs=(P(), osp),
            ))
            og = jax.tree.map(
                lambda x: jnp.zeros((spec.padded,), x.dtype)
                if jnp.ndim(x) else x, st0,
            )
            return step(tree, og, gstack, jnp.float32(0.1))

        # 196 elems / 8 shards: bucket_elems=40 -> 5 buckets of 40
        p_mono, _ = run(flat_spec(tree, 8))
        spec_b = flat_spec(tree, 8, bucket_elems=40)
        assert spec_b.n_buckets == 5
        p_buck, o_buck = run(spec_b)
        for k in tree:
            np.testing.assert_array_equal(
                np.asarray(p_mono[k]), np.asarray(p_buck[k])
            )
        if opt_name == "adam":
            # per-bucket updates share ONE step-counter increment
            assert int(o_buck["t"]) == 1

    def test_bucketed_legacy_closure_matches(self, mesh8, rng):
        """The 2-arg opt_update closure (no opt_state kwarg) still
        runs the pipelined collectives with one full-shard update."""
        opt = opt_lib.momentum()
        tree = self._tree(rng)
        gstack = jnp.asarray(rng.normal(size=(8, 196)), jnp.float32)

        def run(spec):
            st0 = opt.shard_state(spec.shard_len)

            def z1(params, ostate, g, lr):
                def upd(p_s, g_s):
                    return opt.update(p_s, g_s, ostate, lr)

                return scatter_update_gather(
                    params, self._tree_of(g[0]), upd, DATA_AXIS,
                    spec=spec,
                )

            osp = jax.tree.map(
                lambda x: P(DATA_AXIS) if jnp.ndim(x) else P(), st0
            )
            step = jax.jit(shard_map(
                z1, mesh=mesh8,
                in_specs=(P(), osp, P(DATA_AXIS), P()),
                out_specs=(P(), osp),
            ))
            og = jax.tree.map(
                lambda x: jnp.zeros((spec.padded,), x.dtype)
                if jnp.ndim(x) else x, st0,
            )
            return step(tree, og, gstack, jnp.float32(0.1))

        p_mono, _ = run(flat_spec(tree, 8))
        p_buck, _ = run(flat_spec(tree, 8, bucket_elems=40))
        for k in tree:
            np.testing.assert_array_equal(
                np.asarray(p_mono[k]), np.asarray(p_buck[k])
            )

    @pytest.mark.parametrize("two_phase", [False, True])
    def test_bucketed_allreduce_matches_per_leaf(
        self, mesh8, rng, two_phase
    ):
        stacked, trees = _per_device_trees(rng)

        def run(bucket_elems):
            fn = shard_map(
                lambda t: jax.tree.map(
                    lambda x: x[None],
                    allreduce_mean(
                        jax.tree.map(lambda x: x[0], t), DATA_AXIS,
                        two_phase=two_phase, bucket_elems=bucket_elems,
                    ),
                ),
                mesh=mesh8,
                in_specs=P(DATA_AXIS), out_specs=P(DATA_AXIS),
            )
            return jax.jit(fn)(stacked)

        mono, buck = run(0), run(24)   # 101 elems -> several buckets
        want = jax.tree.map(lambda *xs: np.mean(xs, axis=0), *trees)
        for k in ("w", "b"):
            np.testing.assert_allclose(
                np.asarray(buck[k][0]), want[k], rtol=1e-5, atol=1e-6
            )
            np.testing.assert_array_equal(
                np.asarray(mono[k]), np.asarray(buck[k])
            )

    def test_strategy_call_passes_bucket(self, mesh8, rng):
        """ExchangeStrategy.__call__ bucket plumbing + bucket_elems
        conversion from the MB knob."""
        strat = get_strategy("asa32")
        assert strat.bucket_elems(0) == 0
        assert strat.bucket_elems(4) == 4 * 2**20 // 4
        assert strat.bucket_elems(0.25) == 2**18 // 4
        stacked, trees = _per_device_trees(rng)
        fn = shard_map(
            lambda t: jax.tree.map(
                lambda x: x[None],
                strat(jax.tree.map(lambda x: x[0], t), DATA_AXIS, 24),
            ),
            mesh=mesh8, in_specs=P(DATA_AXIS), out_specs=P(DATA_AXIS),
        )
        out = jax.jit(fn)(stacked)
        want = jax.tree.map(lambda *xs: np.mean(xs, axis=0), *trees)
        np.testing.assert_allclose(
            np.asarray(out["w"][0]), want["w"], rtol=1e-5, atol=1e-6
        )


class TestBucketedTraining:
    """End-to-end: exchange_bucket_mb > 0 must reproduce the
    monolithic path's loss trajectory bitwise (ISSUE 2 acceptance) —
    Llama (zero1 + asa32) fast at 25 steps, 50-step Llama + AlexNet
    in the slow tier (same pattern as TestZero1Training)."""

    LLAMA_CFG = dict(
        dim=32, n_layers=2, n_heads=4, n_kv_heads=2, ffn_dim=64,
        vocab=64, seq_len=16, batch_size=2, compute_dtype="float32",
        n_epochs=1, seed=3, lr=1e-3,
    )

    def _llama_losses(self, strategy, bucket_mb, steps, devices, tp=1):
        from theanompi_tpu.models.llama import Llama
        from theanompi_tpu.utils import Recorder

        cfg = dict(self.LLAMA_CFG, exch_strategy=strategy, tp=tp,
                   exchange_bucket_mb=bucket_mb, n_train=16 * steps)
        m = Llama(cfg)
        m.build_model(n_replicas=8 // tp)
        m.compile_iter_fns(
            mesh=make_mesh(data=8 // tp, model=tp, devices=devices)
        )
        if bucket_mb:
            # the toy model must actually bucket, or the test is void
            assert m.exchange.bucket_elems > 0
        rec = Recorder(verbose=False)
        for i in range(steps):
            m.train_iter(i, rec)
        rec.flush()
        return np.asarray(rec.train_losses)

    @pytest.mark.parametrize("strategy", ["zero1", "asa32"])
    def test_llama_bucketed_matches_monolithic(self, devices8, strategy):
        # ~22.6k params: 0.01 MiB buckets -> ~9 buckets
        mono = self._llama_losses(strategy, 0, 25, devices8)
        buck = self._llama_losses(strategy, 0.01, 25, devices8)
        assert np.all(np.isfinite(mono))
        np.testing.assert_array_equal(buck, mono)

    @pytest.mark.parametrize("strategy", ["zero1", "ici16"])
    def test_llama_bucketed_under_tensor_parallel(
        self, devices8, strategy
    ):
        """dp=4 x tp=2 under the vma-checked Llama step: the flat
        buckets mix model-sharded weights with norm weights that are
        replicated over ``model``, and every leaf must come back out
        typed as it went in (``exchange._narrow_vma``) — with the
        exchange itself unchanged.  ``ici16`` at the default bucket
        size is what the Llama proxy of chip_smoke.py runs."""
        mono = self._llama_losses(strategy, 0, 6, devices8, tp=2)
        buck = self._llama_losses(strategy, 0.01, 6, devices8, tp=2)
        assert np.all(np.isfinite(mono))
        np.testing.assert_allclose(buck, mono, rtol=1e-5)

    @pytest.mark.slow
    @pytest.mark.parametrize("strategy", ["zero1", "asa32"])
    def test_llama_bucketed_matches_monolithic_50_steps(
        self, devices8, strategy
    ):
        mono = self._llama_losses(strategy, 0, 50, devices8)
        buck = self._llama_losses(strategy, 0.01, 50, devices8)
        assert np.all(np.isfinite(mono))
        np.testing.assert_array_equal(buck, mono)

    @pytest.mark.slow
    @pytest.mark.parametrize("strategy", ["zero1", "asa32"])
    def test_alexnet_bucketed_matches_monolithic_50_steps(
        self, devices8, strategy
    ):
        from theanompi_tpu.models.alex_net import AlexNet
        from theanompi_tpu.utils import Recorder

        losses = {}
        for bmb in (0, 0.25):
            cfg = dict(batch_size=2, crop=67, n_train=16 * 50, n_val=16,
                       n_epochs=1, seed=5, exch_strategy=strategy,
                       exchange_bucket_mb=bmb, lr=0.01)
            m = AlexNet(cfg)
            m.build_model(n_replicas=8)
            m.compile_iter_fns(
                mesh=make_mesh(data=8, devices=devices8)
            )
            if bmb:
                assert m.exchange.bucket_elems > 0
            rec = Recorder(verbose=False)
            for i in range(50):
                m.train_iter(i, rec)
            rec.flush()
            losses[bmb] = np.asarray(rec.train_losses)
        assert np.all(np.isfinite(losses[0]))
        if strategy == "zero1":
            # both arms are reduce-scatter + all-gather over the same
            # packed buffer — bucket order only permutes the internal
            # layout, trajectories bitwise-equal (measured 0.0)
            np.testing.assert_array_equal(losses[0.25], losses[0])
        else:
            # monolithic asa32 mixes the per-leaf psum FALLBACK
            # (leading dims not divisible by 8) with true RS+AG,
            # while the bucketed path is uniformly RS+AG — the two
            # lowerings differ in reduction order at the ulp level,
            # and AlexNet's bf16 compute amplifies that chaotically
            # over 50 steps (measured max rel 5e-5).  Same bound
            # family as PR 1's cross-strategy trajectory tests.
            np.testing.assert_allclose(
                losses[0.25], losses[0], rtol=1e-4
            )

    def test_zero1_bucket_layout_resume_guard(self, devices8, tmp_path):
        """A zero1 optimizer checkpoint is tied to its bucket layout
        (the flat shard order is bucket-major): resuming under a
        DIFFERENT exchange_bucket_mb must refuse loudly in both load
        orders; the same layout resumes fine."""
        from theanompi_tpu.models.wresnet import WResNet
        from theanompi_tpu.utils import Recorder

        cfg = {"batch_size": 4, "depth": 10, "widen": 1,
               "n_train": 32, "n_val": 16, "n_epochs": 1, "seed": 7,
               "exchange_bucket_mb": 0.02}
        mesh = make_mesh(data=8, devices=devices8)

        def build(c):
            m = WResNet(dict(c))
            m.build_model(n_replicas=8)
            m.compile_iter_fns(mesh=mesh, exch_strategy="zero1")
            return m

        m = build(cfg)
        assert m.exchange.zero1_layout[1] > 0          # actually bucketed
        m.save(str(tmp_path / "a"), Recorder(verbose=False))

        # same layout: resumes
        m2 = build(cfg)
        assert m2.load(str(tmp_path / "a"), Recorder(verbose=False))

        # the DANGEROUS case: a bucket size that divides the
        # monolithic padded, so both layouts produce IDENTICAL flat
        # shapes — only the stamped marker can tell them apart
        # (differing-padded mismatches are already refused by the
        # sharded-checkpoint shape check)
        m_mono = build(dict(cfg, exchange_bucket_mb=0))
        padded = m_mono.exchange.zero1_layout[0]
        assert padded % 32 == 0                # 4 buckets, 8 shards
        coincide_mb = padded * 4 / 4 / 2**20   # padded/4 elems, fp32
        m5 = build(dict(cfg, exchange_bucket_mb=coincide_mb))
        assert m5.exchange.zero1_layout == (padded, padded // 4)
        m5.save(str(tmp_path / "b"), Recorder(verbose=False))

        # compile-then-load (THE supported zero1 resume order) across
        # layouts: load refuses despite the shapes matching exactly.
        # (The load-then-compile order already fails structurally for
        # sharded zero1 checkpoints — the restore prototype must be
        # the compiled flat layout.)
        with pytest.raises(ValueError, match="layout"):
            m_mono.load(str(tmp_path / "b"), Recorder(verbose=False))

        # the bucketed arm refuses the monolithic stamp symmetrically
        m_mono2 = build(dict(cfg, exchange_bucket_mb=0))
        m_mono2.save(str(tmp_path / "c"), Recorder(verbose=False))
        m7 = build(dict(cfg, exchange_bucket_mb=coincide_mb))
        with pytest.raises(ValueError, match="layout"):
            m7.load(str(tmp_path / "c"), Recorder(verbose=False))

    def test_worker_bucketed_summary(self, devices8):
        """The BSP worker surfaces the knob and rejects bad values."""
        from theanompi_tpu.workers import bsp_worker

        TINY = {"batch_size": 4, "depth": 10, "widen": 1, "lr": 0.05,
                "n_train": 32, "n_val": 16, "seed": 7, "n_epochs": 1,
                "exchange_bucket_mb": 0.02}
        res = bsp_worker.run(
            devices=list(range(8)),
            modelfile="theanompi_tpu.models.wresnet",
            modelclass="WResNet",
            config=TINY, verbose=False, exch_strategy="zero1",
        )
        assert res["exchange_bucket_mb"] == 0.02
        with pytest.raises(ValueError, match="exchange_bucket_mb"):
            bsp_worker.run(
                devices=list(range(8)),
                modelfile="theanompi_tpu.models.wresnet",
                modelclass="WResNet",
                config=dict(TINY, exchange_bucket_mb=-1),
                verbose=False,
            )


class TestZero1Training:
    """End-to-end: exch_strategy='zero1' must track the default
    allreduce path's loss trajectory exactly (ISSUE 1 acceptance:
    <=1e-5 relative divergence, same seed)."""

    LLAMA_CFG = dict(
        dim=32, n_layers=2, n_heads=4, n_kv_heads=2, ffn_dim=64,
        vocab=64, seq_len=16, batch_size=2, compute_dtype="float32",
        n_epochs=1, seed=3, lr=1e-3,
    )

    def _llama_losses(self, strategy, steps, devices):
        from theanompi_tpu.models.llama import Llama
        from theanompi_tpu.utils import Recorder

        cfg = dict(self.LLAMA_CFG, exch_strategy=strategy,
                   n_train=16 * steps)
        m = Llama(cfg)
        m.build_model(n_replicas=8)
        m.compile_iter_fns(
            mesh=make_mesh(data=8, devices=devices)
        )
        rec = Recorder(verbose=False)
        for i in range(steps):
            m.train_iter(i, rec)
        rec.flush()
        return np.asarray(rec.train_losses)

    def test_llama_matches_allreduce(self, devices8):
        a = self._llama_losses("asa32", 25, devices8)
        z = self._llama_losses("zero1", 25, devices8)
        assert np.all(np.isfinite(a))
        np.testing.assert_allclose(z, a, rtol=1e-5)

    @pytest.mark.slow
    def test_llama_matches_allreduce_50_steps(self, devices8):
        a = self._llama_losses("asa32", 50, devices8)
        z = self._llama_losses("zero1", 50, devices8)
        assert np.all(np.isfinite(a))
        np.testing.assert_allclose(z, a, rtol=1e-5)

    @pytest.mark.slow
    def test_alexnet_matches_allreduce_50_steps(self, devices8):
        """AlexNet (the reference's primary benchmark; momentum + wd)
        under zero1 over 50 steps on the 8-device CPU mesh."""
        from theanompi_tpu.models.alex_net import AlexNet
        from theanompi_tpu.utils import Recorder

        losses = {}
        for s in ("asa32", "zero1"):
            cfg = dict(batch_size=2, crop=67, n_train=16 * 50, n_val=16,
                       n_epochs=1, seed=5, exch_strategy=s, lr=0.01)
            m = AlexNet(cfg)
            m.build_model(n_replicas=8)
            m.compile_iter_fns(
                mesh=make_mesh(data=8, devices=devices8)
            )
            rec = Recorder(verbose=False)
            for i in range(50):
                m.train_iter(i, rec)
            rec.flush()
            losses[s] = np.asarray(rec.train_losses)
        assert np.all(np.isfinite(losses["asa32"]))
        np.testing.assert_allclose(
            losses["zero1"], losses["asa32"], rtol=1e-5
        )

    def test_zero1_compile_after_restore_refuses(
        self, devices8, tmp_path
    ):
        """Compiling with zero1 AFTER restoring a full (replicated)
        optimizer checkpoint must refuse loudly — silently zeroing the
        restored state would resume training from cold m/v."""
        from theanompi_tpu.models.wresnet import WResNet
        from theanompi_tpu.utils import Recorder

        cfg = {"batch_size": 4, "depth": 10, "widen": 1,
               "n_train": 64, "n_val": 32, "n_epochs": 1, "seed": 7}
        mesh = make_mesh(data=8, devices=devices8)
        m = WResNet(cfg)
        m.build_model(n_replicas=8)
        m.compile_iter_fns(mesh=mesh, exch_strategy="ici32")
        m.save(str(tmp_path), Recorder(verbose=False))

        m2 = WResNet(cfg)
        m2.build_model(n_replicas=8)
        assert m2.load(str(tmp_path), Recorder(verbose=False))
        with pytest.raises(ValueError, match="zero1"):
            m2.compile_iter_fns(mesh=mesh, exch_strategy="zero1")
        # the supported order still works: compile first, then load
        m3 = WResNet(cfg)
        m3.build_model(n_replicas=8)
        m3.compile_iter_fns(mesh=mesh, exch_strategy="zero1")

    def test_classifier_worker_zero1(self, devices8):
        """The BSP worker contract path under zero1 (WRN tiny): same
        final loss as the two-phase allreduce run, sharded opt state
        reported strategy in the summary."""
        from theanompi_tpu.workers import bsp_worker

        TINY = {"batch_size": 4, "depth": 10, "widen": 1, "lr": 0.05,
                "lr_schedule": None, "n_train": 128, "n_val": 32,
                "seed": 7, "n_epochs": 1}
        res = {}
        for s in ("asa32", "zero1"):
            res[s] = bsp_worker.run(
                devices=list(range(8)),
                modelfile="theanompi_tpu.models.wresnet",
                modelclass="WResNet",
                config=TINY, verbose=False, exch_strategy=s,
            )
        assert res["zero1"]["exch_strategy"] == "zero1"
        np.testing.assert_allclose(
            res["zero1"]["final_train_loss"],
            res["asa32"]["final_train_loss"],
            rtol=1e-5,
        )


def _exchange_scopes(lowered_text):
    """The distinct ``exchange_b<i>`` scopes of a lowered program
    (``.as_text(debug_info=True)``)."""
    return set(re.findall(r"exchange_b\d+", lowered_text))


def _spy_lowered(model):
    """Make ``model._train_step`` keep the text it lowers to at its
    first call; returns the one-element list that will hold it."""
    real, seen = model._train_step, []

    def spy(*args):
        if not seen:
            seen.append(real.lower(*args).as_text(debug_info=True))
        return real(*args)

    model._train_step = spy
    return seen


class TestGroupOfOne:
    """A replica group of one exchanges nothing (ISSUE 25): the mean
    over one member is the identity and there is no wire, so no flat
    buffer, bucket, cast or unpack is traced; every non-zero1 strategy
    is the same step there.  A group of more than one is untouched."""

    TREE_SHAPES = TestBucketedExchange.TREE_SHAPES     # 196 elements
    _tree = TestBucketedExchange._tree
    PACK_OPS = ("concatenate", "reshape", "convert", "slice", "pad")

    def _lowered(self, mesh, tree, strategy, bucket_elems, check_vma):
        def body(t):
            if check_vma:
                # as the Llama step: grads are typed varying over the
                # data axes and must leave typed invariant
                t = jax.tree.map(
                    lambda x: jax.lax.pcast(x, (DATA_AXIS,), to="varying"),
                    t,
                )
            return get_strategy(strategy)(t, DATA_AXIS, bucket_elems)

        fn = jax.jit(shard_map(body, mesh=mesh, in_specs=P(),
                               out_specs=P(), check_vma=check_vma))
        return fn, fn.lower(tree).as_text(debug_info=True)

    @pytest.mark.parametrize("check_vma", [True, False])
    @pytest.mark.parametrize("bucket_elems", [0, 40])
    @pytest.mark.parametrize(
        "strategy", sorted(n for n, s in STRATEGIES.items() if not s.zero1)
    )
    def test_identity_without_packing(
        self, devices8, rng, strategy, bucket_elems, check_vma
    ):
        tree = self._tree(rng)
        tree["h"] = tree["b"].astype(jnp.bfloat16)
        mesh = make_mesh(data=1, devices=devices8[:1])
        fn, text = self._lowered(
            mesh, tree, strategy, bucket_elems, check_vma
        )
        out = fn(tree)
        for k in tree:
            assert out[k].dtype == tree[k].dtype
            np.testing.assert_array_equal(
                np.asarray(out[k]), np.asarray(tree[k])
            )
        assert not _exchange_scopes(text)
        assert {op: text.count(f"stablehlo.{op} ")
                for op in self.PACK_OPS} == dict.fromkeys(self.PACK_OPS, 0)

    @pytest.mark.parametrize("bucket_elems,n_scopes", [(0, 1), (40, 5)])
    def test_eight_devices_scope_count(
        self, mesh8, rng, bucket_elems, n_scopes
    ):
        """196 elements over 8 replicas: one ``exchange_b0`` per-leaf,
        five 40-element buckets (200 padded) when bucketed — and the
        packing the group of one does without is there."""
        tree = self._tree(rng)
        _, text = self._lowered(mesh8, tree, "ici16", bucket_elems, False)
        assert len(_exchange_scopes(text)) == n_scopes
        assert exchange_bucket_count(196, 8, bucket_elems) == n_scopes
        assert exchange_bucket_count(196, 1, bucket_elems) == 0
        assert text.count("stablehlo.convert ") > 0
        assert bool(text.count("stablehlo.concatenate ")) == bool(
            bucket_elems
        )

    def _llama(self, strategy, devices):
        from theanompi_tpu.models.llama import Llama

        n = len(devices)
        m = Llama(dict(TestBucketedTraining.LLAMA_CFG, n_train=16 * n,
                       exch_strategy=strategy, exchange_bucket_mb=0.01))
        m.build_model(n_replicas=n)
        m.compile_iter_fns(mesh=make_mesh(data=n, devices=devices))
        return m

    def _wresnet(self, strategy, devices):
        from theanompi_tpu.models.wresnet import WResNet

        n = len(devices)
        m = WResNet({"batch_size": 4, "depth": 10, "widen": 1,
                     "n_train": 8 * n, "n_val": 4 * n, "n_epochs": 1,
                     "seed": 7, "exchange_bucket_mb": 0.02})
        m.build_model(n_replicas=n)
        m.compile_iter_fns(mesh=make_mesh(data=n, devices=devices),
                           exch_strategy=strategy)
        return m

    def _two_steps(self, build, strategy, devices):
        from theanompi_tpu.utils import Recorder

        m = build(strategy, devices)
        assert m.exchange.bucket_elems > 0
        text = _spy_lowered(m)
        rec = Recorder(verbose=False)
        for i in range(2):
            m.train_iter(i, rec)
        rec.flush()
        return m, text[0], jax.tree.map(np.asarray, m.params)

    @pytest.mark.parametrize("family", ["_llama", "_wresnet"])
    def test_model_step_on_one_device(self, devices8, family):
        """The vma-checked Llama step and the unchecked classifier
        step on one device: ``ici16`` compiles, has no exchange scope
        and no flat buffer, and after 2 steps its parameters are
        bitwise ``ici32``'s."""
        build = getattr(self, family)
        m, text, p16 = self._two_steps(build, "ici16", devices8[:1])
        _, _, p32 = self._two_steps(build, "ici32", devices8[:1])
        for a, b in zip(jax.tree.leaves(p16), jax.tree.leaves(p32)):
            np.testing.assert_array_equal(a, b)
        assert (m.exchange_replicas, m.exchange_buckets) == (1, 0)
        assert not _exchange_scopes(text)
        size = sum(x.size for x in jax.tree.leaves(p16))
        padded, bucket_len = flat_layout(size, 1, m.exchange.bucket_elems)
        assert bucket_len                       # it would have bucketed
        assert f"tensor<{padded}x" not in text
        assert f"tensor<{bucket_len}x" not in text

    @pytest.mark.parametrize("family", ["_llama", "_wresnet"])
    def test_model_step_on_eight_devices(self, devices8, family):
        """The same models on eight devices still bucket, and the
        summary's count is the number of scopes the step traced."""
        m, text, params = self._two_steps(
            getattr(self, family), "ici16", devices8
        )
        size = sum(x.size for x in jax.tree.leaves(params))
        assert m.exchange_replicas == 8
        assert m.exchange_buckets == exchange_bucket_count(
            size, 8, m.exchange.bucket_elems
        ) > 1
        assert len(_exchange_scopes(text)) == m.exchange_buckets
        assert f"tensor<{flat_layout(size, 8, m.exchange.bucket_elems)[1]}x" in text

    @pytest.mark.parametrize("n_devices", [1, 8])
    def test_worker_summary_counts(self, n_devices):
        from theanompi_tpu.workers import bsp_worker

        res = bsp_worker.run(
            devices=list(range(n_devices)),
            modelfile="theanompi_tpu.models.wresnet",
            modelclass="WResNet",
            config={"batch_size": 4, "depth": 10, "widen": 1, "lr": 0.05,
                    "n_train": 4 * n_devices, "n_val": 4 * n_devices,
                    "seed": 7, "n_epochs": 1, "exchange_bucket_mb": 0.02},
            verbose=False, exch_strategy="ici16",
        )
        size = sum(x.size for x in jax.tree.leaves(res["model"].params))
        k = exchange_bucket_count(size, n_devices, res["model"].exchange.bucket_elems)
        assert res["exchange_replicas"] == n_devices
        assert res["exchange_buckets"] == k
        assert (k == 0) if n_devices == 1 else (k > 1)


class TestConsistencyCheck:
    def test_delta_zero_when_synced(self, mesh8, rng):
        t = _tree(rng)
        stacked = jax.tree.map(lambda x: jnp.broadcast_to(x, (8,) + x.shape), t)
        fn = shard_map(
            lambda s: replica_consistency_delta(
                jax.tree.map(lambda x: x[0], s), DATA_AXIS
            )[None],
            mesh=mesh8, in_specs=P(DATA_AXIS), out_specs=P(DATA_AXIS),
        )
        delta = jax.jit(fn)(stacked)
        assert float(np.max(np.asarray(delta))) < 1e-6

    def test_delta_positive_when_diverged(self, mesh8, rng):
        stacked, _ = _per_device_trees(rng)
        fn = shard_map(
            lambda s: replica_consistency_delta(
                jax.tree.map(lambda x: x[0], s), DATA_AXIS
            )[None],
            mesh=mesh8, in_specs=P(DATA_AXIS), out_specs=P(DATA_AXIS),
        )
        delta = jax.jit(fn)(stacked)
        assert float(np.max(np.asarray(delta))) > 0.1


class TestWireDtypeEdges:
    """wire_dtype edge cases in flat_pack/scatter_update_gather
    (ISSUE 4 satellite): integer leaves, zero-size leaves under cast,
    and the bitwise fp32-wire == no-wire-dtype identity."""

    def _sug(self, mesh8, params, grads_stacked, wire_dtype):
        spec = flat_spec(params, 8)

        def body(p, g):
            local_p = jax.tree.map(lambda x: x[0], p)
            local_g = jax.tree.map(lambda x: x[0], g)

            def upd(ps, gs):
                return (ps - 0.1 * gs).astype(ps.dtype), ()

            np_, _ = scatter_update_gather(
                local_p, local_g, upd, DATA_AXIS,
                wire_dtype=wire_dtype, spec=spec,
            )
            return jax.tree.map(lambda x: x[None], np_)

        fn = shard_map(
            body, mesh=mesh8,
            in_specs=(P(DATA_AXIS), P(DATA_AXIS)),
            out_specs=P(DATA_AXIS),
            check_vma=False,
        )
        stacked_p = jax.tree.map(
            lambda x: jnp.stack([x] * 8), params
        )
        return jax.jit(fn)(stacked_p, grads_stacked)

    def test_fp32_wire_bitwise_equals_no_wire(self, mesh8, rng):
        """wire_dtype=jnp.float32 must be the IDENTITY cast: bitwise
        the same collective as wire_dtype=None, in both allreduce_mean
        and scatter_update_gather."""
        stacked, _ = _per_device_trees(rng)

        def mean(wire):
            fn = shard_map(
                lambda t: jax.tree.map(
                    lambda x: x[None],
                    allreduce_mean(
                        jax.tree.map(lambda x: x[0], t), DATA_AXIS,
                        wire_dtype=wire,
                    ),
                ),
                mesh=mesh8, in_specs=P(DATA_AXIS),
                out_specs=P(DATA_AXIS),
            )
            return jax.jit(fn)(stacked)

        a, b = mean(None), mean(jnp.float32)
        for k in ("w", "b"):
            np.testing.assert_array_equal(np.asarray(a[k]),
                                          np.asarray(b[k]))

        params = _tree(rng)
        p_none = self._sug(mesh8, params, stacked, None)
        p_f32 = self._sug(mesh8, params, stacked, jnp.float32)
        for k in ("w", "b"):
            np.testing.assert_array_equal(np.asarray(p_none[k]),
                                          np.asarray(p_f32[k]))

    def test_integer_leaves_under_wire_cast(self, mesh8, rng):
        """An int32 leaf rides the fp32 master buffer through the cast
        wire and restores its dtype and (identity-update) values
        exactly — int magnitudes small enough for bf16 to hold."""
        params = {
            "w": jnp.asarray(rng.normal(size=(6,)), jnp.float32),
            "step": jnp.arange(4, dtype=jnp.int32),
        }
        grads = {
            "w": jnp.asarray(rng.normal(size=(6,)), jnp.float32),
            "step": jnp.zeros((4,), jnp.int32),
        }
        stacked_g = jax.tree.map(lambda x: jnp.stack([x] * 8), grads)
        spec = flat_spec(params, 8)
        assert spec.dtype == jnp.float32

        def body(p, g):
            local_p = jax.tree.map(lambda x: x[0], p)
            local_g = jax.tree.map(lambda x: x[0], g)

            def upd(ps, gs):
                return ps, ()          # identity: dtype round-trip only

            np_, _ = scatter_update_gather(
                local_p, local_g, upd, DATA_AXIS,
                wire_dtype=jnp.bfloat16, spec=spec,
            )
            return jax.tree.map(lambda x: x[None], np_)

        fn = shard_map(
            body, mesh=mesh8,
            in_specs=(P(DATA_AXIS), P(DATA_AXIS)),
            out_specs=P(DATA_AXIS), check_vma=False,
        )
        stacked_p = jax.tree.map(lambda x: jnp.stack([x] * 8), params)
        out = jax.jit(fn)(stacked_p, stacked_g)
        assert out["step"].dtype == jnp.int32
        np.testing.assert_array_equal(np.asarray(out["step"][0]),
                                      np.arange(4, dtype=np.int32))
        np.testing.assert_array_equal(np.asarray(out["w"][0]),
                                      np.asarray(params["w"]))

    def test_zero_size_leaf_under_wire_cast(self, mesh8, rng):
        """A (0,)-shaped leaf must survive the bf16 wire cast in both
        exchange shapes (the cast maps over every leaf — an empty one
        must not break pack/concat/collective lowering)."""
        tree = {
            "w": jnp.asarray(rng.normal(size=(4, 2)), jnp.float32),
            "empty": jnp.zeros((0,), jnp.float32),
        }
        stacked = jax.tree.map(lambda x: jnp.stack([x] * 8), tree)

        fn = shard_map(
            lambda t: jax.tree.map(
                lambda x: x[None],
                allreduce_mean(
                    jax.tree.map(lambda x: x[0], t), DATA_AXIS,
                    wire_dtype=jnp.bfloat16,
                ),
            ),
            mesh=mesh8, in_specs=P(DATA_AXIS), out_specs=P(DATA_AXIS),
        )
        out = jax.jit(fn)(stacked)
        assert out["empty"].shape == (8, 0)
        np.testing.assert_allclose(
            np.asarray(out["w"][0]), np.asarray(tree["w"]),
            rtol=1e-2,
        )

        p2 = self._sug(mesh8, tree, stacked, jnp.bfloat16)
        assert p2["empty"].shape == (8, 0)
