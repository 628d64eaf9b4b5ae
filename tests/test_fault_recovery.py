"""Preemption / fault recovery (SURVEY §5.3, VERDICT r1 item 9).

The reference's failure story: checkpoint every epoch, restart from
the last one.  Prove the rebuild honors it end-to-end — and (PR 3)
that the SUPERVISOR closes the loop without an operator:

- manual kill-and-rerun (the original drill, kept verbatim),
- one supervised ``launch()`` surviving an injected ``die``, ``hang``
  and ``corrupt_ckpt`` in a single invocation — zero operator action,
  loss decreasing across every recovery, the report naming each
  restart's cause and resumed-from step,
- graceful SIGTERM preemption losing ZERO steps (mid-epoch
  checkpoint + mid-epoch resume),
- post-commit corruption quarantined and fallen back from, in BOTH
  checkpoint formats (npz and ``.shards``).

The deterministic grid cells are tagged ``fault_matrix``
(``scripts/fault_matrix.sh`` runs them as a suite).
"""

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parent.parent

CHILD = textwrap.dedent(
    """
    import json, sys
    sys.path.insert(0, {repo!r})
    import os
    import jax
    jax.config.update("jax_default_device", jax.devices("cpu")[0])
    from theanompi_tpu.utils import enable_compile_cache
    enable_compile_cache()
    from theanompi_tpu.workers import bsp_worker
    out = bsp_worker.run(
        devices=list(range(4)),
        modelfile="theanompi_tpu.models.wresnet", modelclass="WResNet",
        config={{"batch_size": 4, "n_epochs": 4, "depth": 10, "widen": 1,
                 "lr": 0.05, "lr_schedule": None,
                 "n_train": 128, "n_val": 32}},
        checkpoint_dir=sys.argv[1],
        resume=(sys.argv[2] == "resume"),
        verbose=True,
    )
    rec = out["recorder"]
    print("RESULT " + json.dumps({{
        "epochs": out["epochs"],
        "losses": [float(x) for x in rec.train_losses],
    }}), flush=True)
    """
).format(repo=str(REPO))


def _run_child(script, ckpt, mode, fault_at=None, timeout=560):
    env = dict(os.environ)
    env.update(
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=4",
    )
    if fault_at:
        env["TM_FAULT_AT"] = fault_at
    else:
        env.pop("TM_FAULT_AT", None)
    return subprocess.run(
        [sys.executable, str(script), str(ckpt), mode],
        capture_output=True, text=True, timeout=timeout, env=env,
    )


@pytest.mark.slow
class TestKillAndResume:
    def test_fault_mid_epoch_then_resume(self, tmp_path):
        script = tmp_path / "child.py"
        script.write_text(CHILD)
        ckpt = tmp_path / "ck"

        # run 1: dies uncleanly in the middle of epoch 1 (epoch 0's
        # checkpoint is already committed)
        r1 = _run_child(script, ckpt, "fresh", fault_at="1:3")
        assert r1.returncode == 137, (r1.returncode, r1.stderr[-2000:])
        assert "injecting fault at epoch 1 iter 3" in r1.stdout
        assert "RESULT" not in r1.stdout  # really died mid-run
        saved = list(ckpt.glob("*"))
        assert saved, "no checkpoint was committed before the fault"

        # run 2: resumes from the epoch-0 checkpoint and completes
        r2 = _run_child(script, ckpt, "resume")
        assert r2.returncode == 0, r2.stderr[-2000:]
        assert "resumed from epoch 0" in r2.stdout, r2.stdout[-1500:]
        line = [l for l in r2.stdout.splitlines()
                if l.startswith("RESULT")][0]
        import json

        res = json.loads(line[len("RESULT "):])
        assert res["epochs"] == 4
        # the restored recorder carries epoch 0's 8 losses from before
        # the death; the resumed process adds epochs 1-3 (24 more) —
        # the curve is CONTINUOUS across the fault
        assert len(res["losses"]) == 8 + 24, len(res["losses"])
        # training continued productively across the death
        assert np.mean(res["losses"][-8:]) < np.mean(res["losses"][:8])

    def test_bad_fault_spec_rejected(self, monkeypatch):
        from theanompi_tpu.utils import faults

        faults.reset_fault_cache()
        monkeypatch.setenv("TM_FAULT_AT", "nonsense")
        with pytest.raises(ValueError, match="TM_FAULT_AT"):
            faults.maybe_inject_fault(0, 0)
        faults.reset_fault_cache()


# ---------------------------------------------------------------------------
# PR 3: supervised self-healing — no operator in the loop
# ---------------------------------------------------------------------------

def _wresnet_kwargs(ckpt, n_epochs, **cfg):
    return dict(
        config={"batch_size": 4, "n_epochs": n_epochs, "depth": 10,
                "widen": 1, "lr": 0.05, "lr_schedule": None,
                "n_train": 128, "n_val": 32, **cfg},
        checkpoint_dir=str(ckpt),
        verbose=True,
    )


def _supervised_launch(ckpt, fault_at, n_epochs, *, stall_timeout_s=25.0,
                       max_restarts=5, **cfg):
    """One supervised launch() with faults injected in the child env —
    the supervisor and assertions run in THIS process; children are
    separate CPU-jax processes."""
    from theanompi_tpu import launcher

    env = dict(os.environ)
    env.update(
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=4",
        PYTHONPATH=str(REPO),
        TM_FAULT_AT=fault_at,
    )
    return launcher.launch(
        "theanompi_tpu.workers.bsp_worker",
        devices=list(range(4)),
        modelfile="theanompi_tpu.models.wresnet",
        modelclass="WResNet",
        mode="supervised",
        rule_kwargs=_wresnet_kwargs(ckpt, n_epochs, **cfg),
        supervise=dict(
            max_restarts=max_restarts,
            stall_timeout_s=stall_timeout_s,
            startup_grace_s=600.0,
            backoff_base_s=0.2,
            backoff_cap_s=1.0,
            poll_interval_s=0.25,
            seed=0,
            env=env,
        ),
    )


def _final_recorder_state(ckpt: Path) -> dict:
    """The newest checkpoint sidecar's recorder history — the full
    loss curve across every restart."""
    sides = sorted(
        ckpt.glob("ckpt_*.json"),
        key=lambda p: int(p.stem.split("_")[1]),
    )
    return json.loads(sides[-1].read_text())["recorder"]


@pytest.mark.slow
@pytest.mark.fault_matrix
class TestSupervisedSelfHealing:
    def test_die_hang_corrupt_single_launch(self, tmp_path):
        """The acceptance drill: one launch() survives a mid-epoch
        die, a hang, and a post-commit checkpoint corruption —
        finishing all epochs with zero operator intervention."""
        ckpt = tmp_path / "ck"
        h = _supervised_launch(
            ckpt, "1:3:die,2:2:hang,3:1:corrupt_ckpt", n_epochs=5
        )
        report = h.wait()

        assert report["completed"]
        assert report["n_restarts"] == 3
        causes = [e["cause"] for e in report["restarts"]]
        assert causes == ["preemption", "hang", "preemption"]
        # every restart names where it resumed from
        assert all(
            e["resumed_from"] is not None for e in report["restarts"]
        )
        # recovery was measured and aggregated
        assert report["mttr_s"] is not None and report["mttr_s"] > 0
        assert report["final_heartbeat"]["status"] == "completed"

        # the corrupted checkpoint was quarantined, never deleted, and
        # never loaded (the resume fell back to the previous one)
        assert any("corrupt" in p.name for p in ckpt.iterdir())

        # loss decreasing across EVERY recovery: per-epoch means of
        # the stitched curve are strictly monotone (the run is
        # deterministic — resumes replay the same batch schedule)
        rec = _final_recorder_state(ckpt)
        losses = np.asarray(rec["train_losses"])
        assert len(losses) == 5 * 8, len(losses)
        epoch_means = losses.reshape(5, 8).mean(axis=1)
        assert np.all(np.diff(epoch_means) < 0), epoch_means
        # restart history rides along in the checkpointed recorder —
        # minus the 'hang' event, which was recorded into exactly the
        # checkpoint the corrupt fault destroyed (rolled-back state
        # rolls back its bookkeeping too; the supervisor report above
        # is the authoritative full history)
        assert [e["cause"] for e in rec["restart_events"]] == [
            "preemption", "preemption",
        ]

    def test_sigterm_preemption_loses_zero_steps(self, tmp_path):
        """Graceful preemption: SIGTERM → checkpoint at the next
        iteration boundary → clean exit → supervised relaunch resumes
        MID-EPOCH.  The loss curve has exactly n_epochs * n_batches
        entries: no step was lost or repeated."""
        ckpt = tmp_path / "ck"
        h = _supervised_launch(ckpt, "1:2:sigterm", n_epochs=3)
        report = h.wait()

        assert report["completed"]
        assert report["n_restarts"] == 1
        (ev,) = report["restarts"]
        assert ev["cause"] == "sigterm"
        assert ev["exit_code"] == 0  # it drained CLEANLY
        assert ev["resumed_from"] == [1, 3]  # mid-epoch, exact iter

        rec = _final_recorder_state(ckpt)
        assert len(rec["train_losses"]) == 3 * 8  # zero lost steps
        assert rec["restart_events"][0]["resumed_iter"] == 3
        # training kept dropping across the drain/resume
        losses = np.asarray(rec["train_losses"])
        assert losses[-8:].mean() < losses[:8].mean()

    def test_corrupt_fallback_sharded_format(self, tmp_path):
        """corrupt_ckpt → quarantine + fallback for the ``.shards``
        format (the npz format is covered by the acceptance drill)."""
        ckpt = tmp_path / "ck"
        h = _supervised_launch(
            ckpt, "2:1:corrupt_ckpt", n_epochs=4,
            checkpoint_format="sharded",
        )
        report = h.wait()

        assert report["completed"]
        assert report["n_restarts"] == 1
        assert report["restarts"][0]["cause"] == "preemption"
        # the corrupted .shards dir was quarantined...
        assert any(
            p.name.endswith(".corrupt") and p.is_dir()
            for p in ckpt.iterdir()
        )
        # ...and healthy sharded checkpoints exist through the end
        from theanompi_tpu.utils import (
            is_sharded_checkpoint,
            latest_checkpoint,
        )

        final = latest_checkpoint(ckpt, validate=True)
        assert final is not None and is_sharded_checkpoint(final)
        assert int(final.name.split("_")[1].split(".")[0]) == 3

    def test_budget_exhaustion_fails_loudly(self, tmp_path):
        """Four faults, budget of two restarts: the supervisor gives
        up with SupervisorGaveUp, not a silent infinite loop."""
        from theanompi_tpu.utils.supervisor import SupervisorGaveUp

        ckpt = tmp_path / "ck"
        h = _supervised_launch(
            ckpt, "0:1:die,0:2:die,0:3:die,0:4:die",
            n_epochs=2, max_restarts=2,
        )
        with pytest.raises(SupervisorGaveUp, match="budget exhausted"):
            h.wait()


# ---------------------------------------------------------------------------
# ISSUE 8: elastic training — resize the world instead of relaunching
# into hardware that isn't coming back
# ---------------------------------------------------------------------------

# Tiny Llama for the elastic drill: RMSNorm (batch-statistics-free),
# fp32 compute, adam + zero1 + bucketed exchange — the trajectory of
# an equal-GLOBAL-batch run is identical across dp widths up to
# reduction order, so the shrink-resume curve is comparable to an
# uninterrupted reference at tight tolerance.
_ELASTIC_CFG = dict(
    dim=32, n_layers=2, n_heads=4, n_kv_heads=2, ffn_dim=64,
    vocab=32, seq_len=32, batch_size=2, n_train=64, n_val=16,
    compute_dtype="float32", remat=False, lr=3e-3,
    exch_strategy="zero1", exchange_bucket_mb=0.02,
    lr_schedule=None,
)


def _elastic_launch(ckpt, n_epochs, *, fault_at=None, resume=False,
                    max_restarts=3, extra_cfg=None, extra_env=None):
    from theanompi_tpu import launcher

    env = dict(os.environ)
    env.update(
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=8",
        PYTHONPATH=str(REPO),
    )
    if fault_at:
        env["TM_FAULT_AT"] = fault_at
    else:
        env.pop("TM_FAULT_AT", None)
    env.pop("TM_LOADER_JOURNAL", None)
    if extra_env:
        env.update(extra_env)
    return launcher.launch(
        "theanompi_tpu.workers.bsp_worker",
        devices=list(range(8)),
        modelfile="theanompi_tpu.models.llama",
        modelclass="Llama",
        rule_kwargs=dict(
            config=dict(_ELASTIC_CFG, n_epochs=n_epochs,
                        **(extra_cfg or {})),
            checkpoint_dir=str(ckpt),
            resume=resume,
            verbose=True,
        ),
        supervise=dict(
            max_restarts=max_restarts,
            stall_timeout_s=120.0,
            startup_grace_s=600.0,
            backoff_base_s=0.2,
            backoff_cap_s=1.0,
            poll_interval_s=0.25,
            seed=0,
            env=env,
        ),
        elastic={"min_dp": 2},
    )


def _final_elastic_recorder(ckpt: Path) -> dict:
    """Recorder history from the newest checkpoint — the zero1 drill
    writes .shards dirs (meta.json inside), not npz sidecars."""
    from theanompi_tpu.utils import checkpoint_meta, latest_checkpoint

    return checkpoint_meta(latest_checkpoint(ckpt, validate=True))[
        "recorder"
    ]


@pytest.mark.slow
@pytest.mark.fault_matrix
class TestElasticWorldResize:
    def test_shrink_resume_then_grow_back(self, tmp_path):
        """The ISSUE 8 acceptance drill: a supervised 8-way run loses
        capacity mid-run (shrink_world), resumes at dp=4 WITHOUT
        manual intervention (resharded zero1 state, global batch held
        constant), trains to completion with a loss curve matching an
        uninterrupted equal-global-batch run within tolerance — then
        a second launch after capacity returns grows back to dp=8."""
        ckpt = tmp_path / "ck"
        n_epochs, nb = 4, 4  # 64 samples / 16 global batch

        h = _elastic_launch(ckpt, n_epochs,
                            fault_at="1:1:shrink_world")
        report = h.wait()

        assert report["completed"]
        assert report["world_size_history"] == [8, 4]
        (ev,) = report["restarts"]
        assert ev["cause"] == "preemption"
        assert ev["world_size"] == 4
        assert ev["resharded"] is True
        assert report["final_heartbeat"]["world_size"] == 4

        rec = _final_elastic_recorder(ckpt)
        losses = np.asarray(rec["train_losses"], np.float64)
        assert len(losses) == n_epochs * nb  # no step lost or doubled
        # world-size history rode through the checkpointed recorder
        assert [e["world_size"] for e in rec["restart_events"]] == [4]
        assert [e["resharded"] for e in rec["restart_events"]] == [True]

        # the uninterrupted equal-global-batch reference (in-process,
        # dp=8 throughout — same global batch schedule, same seeds)
        from theanompi_tpu.workers import bsp_worker

        ref = bsp_worker.run(
            devices=list(range(8)),
            modelfile="theanompi_tpu.models.llama",
            modelclass="Llama",
            config=dict(_ELASTIC_CFG, n_epochs=n_epochs),
            verbose=False,
        )
        ref_losses = np.asarray(
            ref["recorder"].train_losses, np.float64
        )
        assert len(ref_losses) == n_epochs * nb
        # identical math modulo reduction order (fp32, RMSNorm, no
        # quantization): the resized run tracks the reference tightly
        np.testing.assert_allclose(
            losses, ref_losses, rtol=1e-2, atol=1e-3,
        )
        # and it actually trained across the resize
        assert losses[-nb:].mean() < losses[:nb].mean()

        # -- capacity returns: grow back to dp=8 and keep training
        (ckpt / ".world").unlink()
        h2 = _elastic_launch(ckpt, n_epochs + 2, resume=True)
        report2 = h2.wait()
        assert report2["completed"]
        assert report2["world_size_history"] == [8]
        fhb = report2["final_heartbeat"]
        assert fhb["world_size"] == 8
        assert fhb["resharded"] is True  # dp=4 checkpoint regathered
        rec2 = _final_elastic_recorder(ckpt)
        assert len(rec2["train_losses"]) == (n_epochs + 2) * nb


# ---------------------------------------------------------------------------
# ISSUE 16: the data plane under faults — a stalled producer degrades
# (never deadlocks, never reorders), and the pipelined feed rides an
# elastic 8 -> 4 reshard with every sample delivered exactly once
# ---------------------------------------------------------------------------


_STALL_CFG = dict(
    batch_size=4, depth=10, widen=1, n_train=4 * 8 * 4, n_val=32,
    n_epochs=1, lr=0.01, seed=3, lr_schedule=None,
)


def _stall_run(monkeypatch, fault_at=None, stall_n=2):
    from theanompi_tpu.utils import faults
    from theanompi_tpu.workers import bsp_worker

    if fault_at:
        monkeypatch.setenv("TM_FAULT_AT", fault_at)
        monkeypatch.setenv("TM_STALL_LOADER_N", str(stall_n))
    else:
        monkeypatch.delenv("TM_FAULT_AT", raising=False)
    monkeypatch.delenv("TM_FAULT_STATE", raising=False)
    faults.reset_fault_cache()
    try:
        return bsp_worker.run(
            devices=list(range(8)),
            modelfile="theanompi_tpu.models.wresnet",
            modelclass="WResNet",
            config=dict(_STALL_CFG, loader_pipeline=2),
            verbose=False,
        )
    finally:
        monkeypatch.delenv("TM_FAULT_AT", raising=False)
        faults.reset_fault_cache()


@pytest.mark.slow
@pytest.mark.fault_matrix
class TestLoaderStallDrill:
    def test_stalled_producer_degrades_bitwise(self, monkeypatch):
        """``stall_loader`` freezes the producer for N batches
        mid-epoch: the consumer's timeout path must tick ``starved``
        and fetch synchronously — same batches, same order, losses
        BITWISE equal to an unstalled pipelined run."""
        # inject after iter 0: the depth-2 ring holds iters 1-2 and
        # the producer is parked on a full ring with iter 3 (the LAST
        # window) still unfetched, so the stall is always consumed —
        # one iter later the producer has prefetched the whole epoch
        # and the drill would assert on a no-op
        clean = _stall_run(monkeypatch)
        stalled = _stall_run(
            monkeypatch, fault_at="0:0:stall_loader", stall_n=2
        )
        assert stalled["loader"] is not None
        assert stalled["loader"]["starved"] >= 1
        assert clean["loader"]["starved"] == 0
        a = [float(x) for x in clean["recorder"].train_losses]
        b = [float(x) for x in stalled["recorder"].train_losses]
        assert a == b


@pytest.mark.slow
@pytest.mark.fault_matrix
class TestElasticPipelinedFeed:
    def test_shrink_world_mid_epoch_zero_lost_zero_dup(
            self, tmp_path, monkeypatch):
        """The ISSUE 16 elastic drill: a supervised 8-way run with the
        PIPELINED feed loses half its capacity mid-epoch
        (``shrink_world`` at epoch 1 iter 1) and resumes at dp=4.
        World history [8, 4]; the loader journal's FINAL delivery per
        (epoch, iter) window covers each permutation window exactly —
        zero lost, zero duplicated sample ids; the loss curve matches
        an uninterrupted equal-global-batch reference at rtol 1e-2."""
        from theanompi_tpu.data import coverage_check
        from theanompi_tpu.models.data.lm_synthetic import (
            MarkovLMData,
        )

        monkeypatch.delenv("TM_LOADER_JOURNAL", raising=False)
        ckpt = tmp_path / "ck"
        jpath = tmp_path / "journal.jsonl"
        n_epochs, nb = 3, 4
        h = _elastic_launch(
            ckpt, n_epochs, fault_at="1:1:shrink_world",
            extra_cfg={"loader_pipeline": 2},
            extra_env={"TM_LOADER_JOURNAL": str(jpath)},
        )
        report = h.wait()
        assert report["completed"]
        assert report["world_size_history"] == [8, 4]

        entries = [json.loads(l) for l in open(jpath)]
        assert entries, "pipelined feed wrote no journal"
        worlds = sorted({e["world"] for e in entries})
        assert worlds == [4, 8]
        # the relaunch REPLAYS the interrupted epoch from its last
        # checkpoint (non-graceful death), so keep each window's
        # FINAL delivery — the stream the finished run trained on
        final = {}
        for e in entries:
            final[(e["epoch"], e["iter"])] = e
        data = MarkovLMData(
            vocab=_ELASTIC_CFG["vocab"],
            seq_len=_ELASTIC_CFG["seq_len"],
            batch_size=_ELASTIC_CFG["batch_size"],
            n_train=_ELASTIC_CFG["n_train"],
            n_val=_ELASTIC_CFG["n_val"],
            n_replicas=8,
            seed=42,  # the Llama config default — perm must match
        )

        def perm_for_epoch(epoch):
            data.shuffle(epoch)
            return data.epoch_permutation()

        lost, dup = coverage_check(
            list(final.values()),
            global_batch=16,
            n_batch_train=nb,
            perm_for_epoch=perm_for_epoch,
        )
        assert not lost and not dup, (lost[:5], dup[:5])
        # every epoch's full window set was delivered
        assert sorted({k[0] for k in final}) == list(range(n_epochs))

        # trajectory: matches the uninterrupted dp=8 reference
        from theanompi_tpu.workers import bsp_worker

        rec = _final_elastic_recorder(ckpt)
        losses = np.asarray(rec["train_losses"], np.float64)
        assert len(losses) == n_epochs * nb
        ref = bsp_worker.run(
            devices=list(range(8)),
            modelfile="theanompi_tpu.models.llama",
            modelclass="Llama",
            config=dict(_ELASTIC_CFG, n_epochs=n_epochs),
            verbose=False,
        )
        np.testing.assert_allclose(
            losses,
            np.asarray(ref["recorder"].train_losses, np.float64),
            rtol=1e-2, atol=1e-3,
        )
