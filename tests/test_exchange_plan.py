"""``parallel.ExchangePlan``: the one statement of which gradient
exchange a step uses and of everything derived from the choice, held
to the layout rules it is built on (``flat_layout``,
``exchange_bucket_count``) without a device.  The steps it builds are
exercised through the models in ``test_exchange.py``,
``test_compression.py`` and ``test_reshard.py``."""

import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from theanompi_tpu.ops import optimizers as opt_lib
from theanompi_tpu.parallel.exchange import (
    exchange_bucket_count,
    flat_layout,
)
from theanompi_tpu.parallel.strategies import STRATEGIES


class TestExchangePlan:
    """Both families' layouts: the classifier's ``(data,)`` and a
    Llama-like one with every flat axis larger than one."""

    #: (mesh.shape, replica axes, flat axes, per-device pack size)
    LAYOUTS = {
        "classifier": (
            {"pipe": 1, "expert": 1, "data": 8, "model": 1, "seq": 1},
            ("data",), ("data",), 77_001,
        ),
        "llama": (
            {"pipe": 2, "expert": 2, "data": 2, "model": 2, "seq": 1},
            ("expert", "data"), ("pipe", "expert", "data", "model"),
            30_003,
        ),
    }

    def _bound(self, layout, strategy, comp="none", ef=True,
               bucket_mb=0.02, optimizer="adam", **kw):
        from theanompi_tpu.parallel import ExchangePlan

        sizes, replica_axes, flat_axes, n_elems = self.LAYOUTS[layout]
        plan = ExchangePlan.from_config(
            {"exchange_bucket_mb": bucket_mb, "exch_compression": comp,
             "error_feedback": ef}, strategy,
        )
        return plan.bind(
            sizes, n_elems=n_elems, replica_axes=replica_axes,
            flat_axes=flat_axes, optimizer=opt_lib.get(optimizer), **kw
        )

    @pytest.mark.parametrize("layout", sorted(LAYOUTS))
    @pytest.mark.parametrize("ef", [True, False])
    @pytest.mark.parametrize("comp", ["none", "int8", "fp8"])
    @pytest.mark.parametrize("strategy", sorted(STRATEGIES))
    def test_bound_plan_follows_the_layout_rules(
        self, strategy, comp, ef, layout
    ):
        import math

        sizes, replica_axes, flat_axes, n_elems = self.LAYOUTS[layout]
        plan = self._bound(layout, strategy, comp, ef)
        strat = STRATEGIES[strategy]
        n = math.prod(sizes[a] for a in replica_axes)
        devs = math.prod(sizes[a] for a in flat_axes)
        elems = strat.bucket_elems(0.02)
        padded, bucket_len = flat_layout(n_elems, n, elems)
        assert bucket_len > 0                    # the case must bucket
        flat = strat.zero1 or comp != "none"
        assert (plan.padded, plan.bucket_len) == (padded, bucket_len)
        assert plan.exchange_replicas == n
        assert plan.exchange_buckets == exchange_bucket_count(
            n_elems, n, elems, flat=flat
        ) == padded // bucket_len
        assert plan.bucketed
        assert plan.zero1_layout == (
            (padded, bucket_len) if strat.zero1 else None
        )
        carries = comp != "none" and ef
        assert plan.error_feedback == carries
        assert plan.ef_layout == (
            (comp, padded, bucket_len) if carries else None
        )
        # EF residuals: r1 a [padded] per device, r2 (the reduced
        # mean's, absent under zero1) a [padded/n]; global arrays over
        # every flat axis
        want = {}
        if carries:
            want["r1"] = padded * devs
            if not strat.zero1:
                want["r2"] = padded // n * devs
        assert {k: v.shape for k, v in plan.ef_proto.items()} == {
            k: (v,) for k, v in want.items()
        }
        assert plan.ef_specs == {k: P(flat_axes) for k in want}
        if strat.zero1:
            # adam's m, v: one [padded/n] shard a device; t a scalar
            state = plan.init_opt_state()
            assert state["m"].shape == state["v"].shape == (
                padded // n * devs,
            )
            assert state["t"].shape == ()
            assert plan.opt_state_specs == {
                "m": P(flat_axes), "v": P(flat_axes), "t": P()
            }

    @pytest.mark.parametrize("layout", sorted(LAYOUTS))
    def test_group_of_one_and_monolithic(self, layout):
        from theanompi_tpu.parallel import ExchangePlan

        # the default 4 MiB bucket covers these packs: monolithic,
        # and the overlap preset's input stays off
        plan = self._bound(layout, "ici16", bucket_mb=4.0)
        assert (plan.bucket_len, plan.bucketed) == (0, False)
        assert plan.exchange_buckets == 1
        # a replica group of one traces no exchange body unless the
        # path packs at any size (zero1, compression)
        sizes = {"data": 1, "model": 2}
        for strategy, comp, want in (
            ("ici32", "none", 0), ("zero1", "none", 1),
            ("ici32", "int8", 1),
        ):
            one = ExchangePlan.from_config(
                {"exch_compression": comp}, strategy
            ).bind(sizes, n_elems=1000, replica_axes=("data",),
                   flat_axes=("data", "model"),
                   optimizer=opt_lib.get("adam"))
            assert (one.exchange_replicas, one.exchange_buckets) == (1, want)

    @pytest.mark.parametrize("config, strategy, match", [
        ({}, "nccl64", "unknown exch_strategy 'nccl64'; known:"),
        ({"exch_strategy": "zero2"}, None, "unknown exch_strategy 'zero2'"),
        ({"exchange_bucket_mb": -1}, None, "exchange_bucket_mb must be >= 0"),
        ({"exch_compression": "int4"}, None,
         "unknown exch_compression 'int4'"),
    ])
    def test_config_stage_refuses_a_typo(self, config, strategy, match):
        from theanompi_tpu.parallel import ExchangePlan

        with pytest.raises(ValueError, match=match):
            ExchangePlan.from_config(config, strategy)

    def test_config_stage_feeds_summary_and_async_wires(self):
        from theanompi_tpu.parallel import DEFAULT_BUCKET_MB, ExchangePlan

        plan = ExchangePlan.from_config(None)
        assert (plan.strategy.name, plan.bucket_mb, plan.compression,
                plan.error_feedback) == ("ici32", DEFAULT_BUCKET_MB,
                                         None, False)
        assert plan.wire is None
        assert ExchangePlan.from_config({}, "asa16").wire == jnp.bfloat16
        # compression supersedes the strategy's wire dtype
        plan = ExchangePlan.from_config(
            {"exch_strategy": "ici16", "exch_compression": "fp8",
             "error_feedback": False})
        assert (plan.wire, plan.error_feedback) == ("fp8", False)
        # the argument wins over the config key, as in compile_iter_fns
        assert ExchangePlan.from_config(
            {"exch_strategy": "ici16"}, "zero1").zero1

    @pytest.mark.parametrize("strategy, comp, match", [
        ("zero1", "none", "exch_strategy='zero1' does not yet compose"),
        ("zero1_16", "int8", "exch_strategy='zero1' does not yet compose"),
        ("ici32", "int8", "exch_compression does not yet compose"),
        ("asa16", "fp8", "exch_compression does not yet compose"),
    ])
    def test_per_leaf_refuses_flat_buffers(self, strategy, comp, match):
        with pytest.raises(NotImplementedError, match=match):
            self._bound("llama", strategy, comp, per_leaf=True)

    def test_per_leaf_never_buckets(self):
        plan = self._bound("llama", "ici16", per_leaf=True)
        assert plan.bucket_len > 0 and not plan.bucketed
        assert plan.exchange_buckets == 1

    @pytest.mark.parametrize("case", [
        "same_layout", "replicated_tree", "other_bucket_layout",
        "unstamped_under_buckets",
    ])
    def test_zero1_compile_after_restore(self, case):
        plan = self._bound("classifier", "zero1", optimizer="momentum")
        flat = jnp.zeros((plan.padded,), jnp.float32)
        stamp = list(plan.zero1_layout)
        opt_state, restored = {
            "same_layout": (flat, {"zero1_layout": stamp}),
            "replicated_tree": ({"w": jnp.zeros((3, 4))}, {}),
            "other_bucket_layout": (
                flat, {"zero1_layout": [plan.padded, 0]}
            ),
            # a pre-bucketing checkpoint is monolithic
            "unstamped_under_buckets": (flat, {}),
        }[case]
        if case == "same_layout":
            plan.check_restored_opt_state(opt_state, restored)
            return
        with pytest.raises(
            ValueError, match="would silently discard the restored "
                              "optimizer state"
        ):
            plan.check_restored_opt_state(opt_state, restored)

    @pytest.mark.parametrize("case", [
        "nothing_restored", "restored_fits", "orphaned", "other_layout",
        "other_shapes", "no_compression",
    ])
    def test_ef_compile_after_restore(self, case):
        plan = self._bound("llama", "ici32", "int8")
        fits = {k: np.zeros(v.shape, np.float32)
                for k, v in plan.ef_proto.items()}
        stamp = list(plan.ef_layout)
        attached = {"ef_state": True, "ef_layout": stamp}
        if case == "nothing_restored":
            assert not plan.keeps_restored_ef({}, {})
        elif case == "restored_fits":
            assert plan.keeps_restored_ef(fits, attached)
        elif case == "no_compression":
            # nothing to keep, and nothing to refuse
            plain = self._bound("llama", "ici32")
            assert not plain.keeps_restored_ef({}, {"ef_orphaned": True})
        elif case == "orphaned":
            with pytest.raises(ValueError, match="could not attach"):
                plan.keeps_restored_ef({}, {"ef_orphaned": True,
                                            "ef_layout": stamp})
        else:
            ef, restored = {
                "other_layout": (
                    fits, dict(attached, ef_layout=["fp8", *stamp[1:]])
                ),
                "other_shapes": ({"r1": fits["r1"]}, attached),
            }[case]
            with pytest.raises(
                ValueError, match="does not match the compiled "
                                  "exchange layout"
            ):
                plan.keeps_restored_ef(ef, restored)
