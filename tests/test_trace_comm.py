"""Profiler-trace comm attribution (SURVEY §5.1, VERDICT r1 item 6).

Two tiers (VERDICT r2 item 4):

- synthetic XSpace protos with known op intervals — a collective
  fully hidden under compute, one partially exposed — checking the
  classification/overlap math exactly (the TPU device-plane layout);
- a REAL capture: a shard_map'd all-reduce program executed on the
  multi-device CPU mesh, traced with jax.profiler, parsed through the
  same ``comm_report`` — proving the attribution classifies real
  collective timelines, not just fabricated ones.  On XLA:CPU the
  signal lives on per-device executor threads (thunk events named by
  HLO instruction + Rendezvous/Wait coordination stalls).
"""

import pytest

# slow tier: importing the tensorflow-bundled proto costs ~45s alone
pytestmark = pytest.mark.slow

pytest.importorskip("tensorflow.tsl.profiler.protobuf.xplane_pb2")

from theanompi_tpu.utils.trace_comm import (  # noqa: E402
    comm_report,
    is_collective,
)


def _write_trace(tmp_path, events_per_core):
    """events_per_core: list (one per core) of (name, start_ps, dur_ps)."""
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    space = xplane_pb2.XSpace()
    plane = space.planes.add()
    plane.name = "/device:TPU:0"
    names = {}
    for core, events in enumerate(events_per_core):
        line = plane.lines.add()
        line.name = "XLA Ops"
        line.display_name = "XLA Ops"
        line.timestamp_ns = 0
        for name, start, dur in events:
            if name not in names:
                mid = len(names) + 1
                names[name] = mid
                md = plane.event_metadata[mid]
                md.id = mid
                md.name = name
            ev = line.events.add()
            ev.metadata_id = names[name]
            ev.offset_ps = start
            ev.duration_ps = dur
    run = tmp_path / "plugins" / "profile" / "run1"
    run.mkdir(parents=True)
    (run / "host.xplane.pb").write_bytes(space.SerializeToString())
    return tmp_path


class TestClassification:
    def test_collective_names(self):
        assert is_collective("all-reduce.1")
        assert is_collective("all-reduce-start.3")
        assert is_collective("collective-permute-done.2")
        assert is_collective("reduce-scatter.7")
        assert is_collective("all-to-all.1")
        assert not is_collective("fusion.123")
        assert not is_collective("convolution.4")
        assert not is_collective("reduce.9")  # plain reduce is compute


class TestOverlapMath:
    def test_hidden_and_exposed_comm(self, tmp_path):
        # core timeline (ps):
        #   compute   [0, 1000)
        #   all-reduce [500, 1500): 500 hidden under compute, 500 exposed
        #   all-gather [200, 700): fully hidden
        d = _write_trace(tmp_path, [[
            ("fusion.1", 0, 1000),
            ("all-reduce.1", 500, 1000),
            ("all-gather.1", 200, 500),
        ]])
        rep = comm_report(str(d))
        ps = 1e-12
        assert rep["n_cores"] == 1
        assert rep["device_busy_s"] == pytest.approx(1500 * ps)
        assert rep["collective_s"] == pytest.approx(1300 * ps)
        assert rep["exposed_comm_s"] == pytest.approx(500 * ps)
        assert rep["hidden_comm_s"] == pytest.approx(800 * ps)
        assert rep["exposed_comm_frac"] == pytest.approx(500 / 1500)
        assert rep["comm_frac"] == pytest.approx(1300 / 1500)
        # the explicit overlapped-vs-exposed split (bucketed-exchange
        # A/B surface): overlapped == hidden, frac is of COLLECTIVE
        # time (800 of the 1300 collective ps ran under compute)
        assert rep["overlapped_comm_s"] == pytest.approx(800 * ps)
        assert rep["overlapped_comm_frac"] == pytest.approx(800 / 1300)
        assert rep["top_collectives"][0][0] == "all-reduce.1"

    def test_collective_stall_on_one_core_is_exposed(self, tmp_path):
        """Overlap is SAME-CORE: a collective stalling core 0 is
        exposed even while core 1 computes (pooling cores before the
        subtraction would wrongly call it hidden)."""
        d = _write_trace(tmp_path, [
            [("all-reduce.1", 0, 1000)],   # core 0: stalled in comm
            [("fusion.1", 0, 1000)],       # core 1: computing
        ])
        rep = comm_report(str(d))
        ps = 1e-12
        assert rep["n_cores"] == 2
        # busy is core-seconds: 2 cores x 1000ps
        assert rep["device_busy_s"] == pytest.approx(2000 * ps)
        assert rep["exposed_comm_s"] == pytest.approx(1000 * ps)
        assert rep["exposed_comm_frac"] == pytest.approx(0.5)

    def test_pure_compute(self, tmp_path):
        d = _write_trace(tmp_path, [[("fusion.1", 0, 1000)]])
        rep = comm_report(str(d))
        assert rep["collective_s"] == 0.0
        assert rep["exposed_comm_frac"] == 0.0
        # no collective time: the overlapped share is defined as 0
        assert rep["overlapped_comm_frac"] == 0.0

    def test_fully_serialized_tail_vs_fully_hidden(self, tmp_path):
        """The two poles the bucketed A/B distinguishes: a collective
        AFTER all compute (the monolithic exchange tail) is 0%
        overlapped; one fully UNDER compute is 100%."""
        tail = _write_trace(tmp_path / "tail", [[
            ("fusion.1", 0, 1000),
            ("all-reduce.1", 1000, 500),
        ]])
        rep = comm_report(str(tail))
        assert rep["overlapped_comm_frac"] == 0.0
        assert rep["exposed_comm_s"] == pytest.approx(500e-12)
        hidden = _write_trace(tmp_path / "hidden", [[
            ("fusion.1", 0, 1000),
            ("all-reduce.1", 200, 500),
        ]])
        rep = comm_report(str(hidden))
        assert rep["overlapped_comm_frac"] == 1.0
        assert rep["exposed_comm_s"] == 0.0

    def test_no_trace_raises(self, tmp_path):
        with pytest.raises(FileNotFoundError):
            comm_report(str(tmp_path))


class TestRealCollectives:
    """A non-synthetic timeline: real all-reduces, really traced."""

    def test_cpu_mesh_allreduce_attribution(self, tmp_path):
        import jax
        import jax.numpy as jnp
        import numpy as np
        from jax.sharding import Mesh, PartitionSpec as P

        from theanompi_tpu.utils.trace_comm import capture_trace

        devs = jax.devices("cpu")
        if len(devs) < 2:
            pytest.skip("needs a multi-device CPU mesh")
        mesh = Mesh(np.array(devs), ("data",))

        def step(x):
            # compute (matmul) + THE exchange (psum), the BSP shape
            y = x @ x.T
            return jax.lax.psum(y, "data")

        fn = jax.jit(jax.shard_map(
            step, mesh=mesh, in_specs=P("data"), out_specs=P()
        ))
        x = jnp.ones((8 * len(devs), 128), jnp.float32)
        float(fn(x)[0, 0])  # compile + settle outside the capture

        def run():
            out = None
            for _ in range(3):
                out = fn(x)
            float(out[0, 0])  # value-read fence INSIDE the capture

        capture_trace(run, str(tmp_path))
        rep = comm_report(str(tmp_path))

        assert rep["n_cores"] >= len(devs), rep
        assert rep["device_busy_s"] > 0.0
        # the all-reduce must be visible as collective time...
        assert rep["collective_s"] > 0.0, rep
        # ...with a sane exposed/hidden split
        assert 0.0 <= rep["exposed_comm_s"] <= rep["collective_s"] + 1e-12
        assert rep["hidden_comm_s"] == pytest.approx(
            rep["collective_s"] - rep["exposed_comm_s"]
        )
        assert 0.0 < rep["comm_frac"] <= 1.0
        assert rep["top_collectives"], rep

    def test_cpu_mesh_ep_alltoall_attribution(self, tmp_path):
        """The MoE dispatch's all_to_all over the expert axis shows
        up as collective time — EP traffic is observable by the same
        comm-attribution report as every other axis."""
        import jax
        import jax.numpy as jnp
        import numpy as np
        from jax.sharding import Mesh, PartitionSpec as P

        from theanompi_tpu.parallel.moe import moe_ffn
        from theanompi_tpu.utils.trace_comm import capture_trace

        devs = jax.devices("cpu")
        if len(devs) < 2:
            pytest.skip("needs a multi-device CPU mesh")
        mesh = Mesh(np.array(devs[:2]), ("expert",))
        e, d, f = 4, 32, 64
        ks = jax.random.split(jax.random.PRNGKey(0), 5)
        # batch sharded over the expert axis (EP ranks are DP ranks);
        # expert weights sharded on their leading expert dim
        x = jax.random.normal(ks[0], (4, 64, d), jnp.float32)
        router = 0.1 * jax.random.normal(ks[1], (d, e))
        wg = 0.1 * jax.random.normal(ks[2], (e, d, f))
        wu = 0.1 * jax.random.normal(ks[3], (e, d, f))
        wd = 0.1 * jax.random.normal(ks[4], (e, f, d))

        def step(x, router, wg, wu, wd):
            y, _ = moe_ffn(
                x, router, wg, wu, wd,
                n_experts=e, top_k=2, capacity_factor=2.0,
                expert_axis="expert", model_axis=None,
                batch_axes=("expert",),
            )
            return jax.lax.pmean(jnp.sum(y * y), "expert")

        fn = jax.jit(jax.shard_map(
            step, mesh=mesh,
            in_specs=(
                P("expert"), P(), P("expert"), P("expert"), P("expert"),
            ),
            out_specs=P(),
        ))
        float(fn(x, router, wg, wu, wd))  # compile outside the capture

        def run():
            out = None
            for _ in range(3):
                out = fn(x, router, wg, wu, wd)
            float(out)  # value-read fence INSIDE the capture

        capture_trace(run, str(tmp_path))
        rep = comm_report(str(tmp_path))
        assert rep["n_cores"] >= 2, rep
        assert rep["collective_s"] > 0.0, rep


class TestQuantAttribution:
    def test_scope_op_names_extracts_marked_instructions(self):
        from theanompi_tpu.utils.trace_comm import scope_op_names

        hlo = '''
HloModule jit_step
%fused_q {
  ROOT %multiply.4 = f32[8]{0} multiply(...), metadata={op_name="jit(step)/quantize_wire/div" source_file="x.py"}
}
ENTRY %main {
  %convert_slice_fusion.2 = s8[8]{0} fusion(...), kind=kLoop, calls=%fused_q, metadata={op_name="jit(step)/quantize_wire/convert_element_type"}
  %broadcast_multiply_fusion = f32[8]{0} fusion(...), metadata={op_name="jit(step)/dequantize_wire/mul"}
  %dot.7 = f32[8,8]{1,0} dot(...), metadata={op_name="jit(step)/matmul"}
  %all-to-all.4 = s8[8]{0} all-to-all(...), metadata={op_name="jit(step)/all_to_all"}
}
'''
        names = scope_op_names(hlo)
        assert "convert_slice_fusion.2" in names
        assert "broadcast_multiply_fusion" in names
        assert "multiply.4" in names        # fused-computation root
        assert "dot.7" not in names
        assert "all-to-all.4" not in names

    def test_hlo_instruction_names_covers_unmarked_ops(self):
        """The cross-module collision subtrahend must include EVERY
        instruction name, op_name metadata or not — a foreign
        module's bare 'fusion.1' still emits trace events."""
        from theanompi_tpu.utils.trace_comm import hlo_instruction_names

        hlo = '''
HloModule jit_prefill
ENTRY %main {
  %fusion.1 = f32[8]{0} fusion(...), metadata={op_name="jit(prefill)/attn"}
  %dot.7 = f32[8,8]{1,0} dot(...)
  ROOT %tuple.2 = (f32[8]{0}) tuple(%fusion.1)
}
'''
        names = hlo_instruction_names(hlo)
        assert {"fusion.1", "dot.7", "tuple.2"} <= names

    def test_comm_report_sums_quant_ops(self, tmp_path):
        """quant ops count as compute for the hidden/exposed split AND
        sum into quant_s."""
        from theanompi_tpu.utils.trace_comm import comm_report

        # one core: 100ps collective, then 50ps quantize, 150ps dot
        # (events are (name, start_ps, duration_ps))
        _write_trace(tmp_path, [[
            ("all-reduce.1", 0, 100),
            ("quant_fusion.1", 100, 50),
            ("dot.1", 150, 150),
        ]])
        rep = comm_report(str(tmp_path), quant_ops={"quant_fusion.1"})
        assert rep["quant_s"] == pytest.approx(50e-12)
        assert rep["quant_frac"] == pytest.approx(50.0 / 300.0)
        # quant time is compute: it does NOT join the collective set
        assert rep["collective_s"] == pytest.approx(100e-12)
        # and without the op set the field is zero, not absent
        rep0 = comm_report(str(tmp_path))
        assert rep0["quant_s"] == 0.0

    def test_tfrt_cpu_lanes_recognized(self):
        """The XLA:CPU thunk lanes on this image are named
        tf_XLATfrtCpuClient/... — their absence from the lane filter
        was why CPU-mesh traces reported zero cores (and a null
        exposed_comm_frac)."""
        from theanompi_tpu.utils.trace_comm import CPU_LANE_PREFIXES

        for lane in (
            "tf_XLATfrtCpuClient/-2001582753",
            "tf_XLAPjRtCpuClient/123",
            "tf_XLAEigen/7",
        ):
            assert lane.lower().startswith(CPU_LANE_PREFIXES), lane
