"""EASGD: asynchronous elastic-averaging SGD (Zhang et al. 2015).

Reference: ``theanompi/easgd_server.py`` + ``easgd_worker.py`` —
a dedicated server process holds the center parameters and serialises
worker requests; each worker runs ``tau`` local SGD steps then does an
MPI Sendrecv elastic exchange (``w_i -= α(w_i − w_c)`` worker-side,
``w_c += α(w_i − w_c)`` server-side); the server also runs validation
on the center weights and owns the checkpoint (SURVEY §3.2).

TPU-native shape: the "server" is not a process — the center is a
replicated ``jax.Array`` pytree owned by the controller, and the N
workers are per-device replicas with a stacked sharded worker axis
(``ReplicaEngine``).  Every ``tau`` batches the controller dispatches
one jitted ``elastic_center_merge``: each worker pulls against the same
center snapshot and the center absorbs the summed pushes — equivalent
to the reference's request queue draining within one cadence window,
but executed as a single cross-device reduce over ICI instead of N
serialized Sendrecvs over PCIe/IB.

Validation + checkpoint use the center weights (server semantics);
``comm`` wall-clock in the recorder is the real host-dispatched
exchange time, matching the reference's measurement.
"""

from __future__ import annotations

import os
from functools import partial
from typing import Any, Sequence

import jax
import jax.numpy as jnp

import numpy as np

from theanompi_tpu import launcher as _launcher
from theanompi_tpu.data import engine_feed as _engine_feed
from theanompi_tpu.parallel import (
    elastic_center_merge,
    elastic_center_merge_masked,
)
from theanompi_tpu.utils import Recorder, faults as _faults
from theanompi_tpu.utils import supervisor as _sup
from theanompi_tpu.workers.bsp_worker import _build_mesh, _resolve_model
from theanompi_tpu.workers.replica_engine import ReplicaEngine


def _check_stability(
    alpha: float, n_workers: int, allow_unstable: bool = False
) -> None:
    """Synchronous EASGD center step is c += sum_i alpha*(w_i - c);
    the effective center rate beta = alpha*N must be <= 1 (Zhang et
    al. 2015, §4 stability condition) or the center oscillates and
    diverges.  Hard error by default: a diverging config would burn a
    full run behind a warning that scrolls away.  Pass
    ``allow_unstable=True`` in the config to proceed anyway (e.g. to
    study the divergence)."""
    if alpha * n_workers <= 1.0:
        return
    msg = (
        f"EASGD alpha={alpha} with {n_workers} workers gives "
        f"beta={alpha * n_workers:.2f} > 1: unstable. Use "
        f"alpha <= {1.0 / n_workers:.4f}, or set "
        f"allow_unstable=True to proceed anyway."
    )
    if not allow_unstable:
        raise ValueError(msg)
    import warnings

    warnings.warn(msg, stacklevel=3)


def run(
    devices: Sequence[Any] | None = None,
    modelfile: str = "",
    modelclass: str = "",
    *,
    config: dict | None = None,
    alpha: float | None = None,
    tau: int | None = None,
    server_device: Any = None,  # reference API compat; center is virtual
    n_epochs: int | None = None,
    checkpoint_dir: str | None = None,
    resume: bool = False,
    print_freq: int = 40,
    verbose: bool = True,
    speeds: Sequence[float] | None = None,
    center_addr: str | None = None,
    **extra: Any,
) -> dict:
    """Train ``modelclass`` under EASGD; returns a summary dict.

    ``alpha`` — elastic coupling strength (reference default: the
    moving-rate config knob, commonly ``alpha = 1/N``); ``tau`` —
    local steps between exchanges (reference default 1–16).

    ``speeds`` — per-worker relative speeds in (0, 1] (out-of-step
    mode): worker w advances one local step per tick with rate
    ``speeds[w]`` and exchanges with the center when ITS OWN counter
    hits ``tau`` — workers genuinely run different step counts between
    exchanges, the reference's defining asynchrony (SURVEY §3.2).

    When launched across processes (``jax.distributed`` via
    tmlauncher), each PROCESS is one EASGD worker over its local chips
    and exchanges with a TCP center server on process 0
    (``parallel/center_server.py``) at its own cadence — no barrier.
    ``center_addr`` ("host:port") pins the server address; default
    publishes it through the jax.distributed KV store.
    """
    del server_device  # no dedicated chip needed: center is replicated
    import jax as _jax

    if _jax.process_count() > 1:
        if speeds is not None:
            raise ValueError(
                "speeds= is a single-controller knob (masked per-device "
                "replicas); in multi-process mode each process already "
                "runs at its own natural pace — drop the argument"
            )
        return _run_distributed(
            modelfile=modelfile,
            modelclass=modelclass,
            config={**(config or {}), **extra},
            alpha=alpha,
            tau=tau,
            n_epochs=n_epochs,
            checkpoint_dir=checkpoint_dir,
            resume=resume,
            print_freq=print_freq,
            verbose=verbose,
            center_addr=center_addr,
        )
    mesh = _build_mesh(devices)
    n_workers = mesh.shape["data"]

    Model = _resolve_model(modelfile, modelclass)
    cfg = dict(config or {})
    cfg.update(extra)
    if n_epochs is not None:
        cfg["n_epochs"] = n_epochs

    alpha = float(alpha if alpha is not None
                  else cfg.get("alpha", 1.0 / n_workers))
    tau = int(tau if tau is not None else cfg.get("tau", 4))
    _check_stability(alpha, n_workers, cfg.get("allow_unstable", False))

    model = Model(cfg)
    model.build_model(n_replicas=n_workers)

    recorder = Recorder(
        rank=0, size=n_workers, print_freq=print_freq, verbose=verbose
    )
    # mid-epoch resumes restart from the center-adopted checkpoint;
    # out-of-step speed credits restart at zero — a small perturbation
    # of an already-asynchronous schedule
    start_iter, resumed_from = _sup.begin_resilient_run(
        model, recorder, checkpoint_dir, resume, verbose=verbose
    )

    # ReplicaEngine stacks model.params — which the load above has
    # already replaced on resume, so workers restart from the restored
    # center (with the checkpointed consensus momentum) automatically.
    engine = ReplicaEngine(model, mesh)
    center = jax.device_put(model.params, engine.replicated)

    @partial(jax.jit, donate_argnums=(0, 1))
    def exchange(stacked, c):
        return elastic_center_merge(stacked, c, alpha)

    @partial(jax.jit, donate_argnums=(0, 1))
    def exchange_masked(stacked, c, mask):
        return elastic_center_merge_masked(stacked, c, alpha, mask)

    if speeds is not None:
        speeds_arr = np.asarray(speeds, np.float64)
        if speeds_arr.shape != (n_workers,):
            raise ValueError(
                f"speeds must have one entry per worker "
                f"({n_workers}); got shape {speeds_arr.shape}"
            )
        if np.any(speeds_arr <= 0) or np.any(speeds_arr > 1):
            raise ValueError("speeds must lie in (0, 1]")
        credit = np.zeros(n_workers)
        since_exchange = np.zeros(n_workers, np.int64)
        local_steps = np.zeros(n_workers, np.int64)

    data = model.data
    # pipelined feed (loader_pipeline knob): batches staged by a
    # producer thread onto the engine's worker-axis sharding, consumed
    # by train_step_staged — the same A/B as the BSP model's _feed
    feed = _engine_feed(
        cfg, data, engine,
        epoch_of=lambda: model.epoch, world=n_workers,
    )
    if verbose:
        print(
            f"EASGD: {n_workers} workers, alpha={alpha:.4f} tau={tau}, "
            f"{data.n_batch_train} train batches x {data.global_batch} "
            f"global batch",
            flush=True,
        )

    step = 0
    n_exchanges = 0

    def _quiesce() -> None:
        """Fence in-flight train/exchange programs before dispatching
        another multi-device program (per-leaf means, validation):
        the race can starve XLA:CPU's rendezvous on low-core hosts,
        and value reads are the only honest fence on this image — see
        base.py.  The flush materializes pending train metrics; the
        center read fences the last elastic exchange."""
        recorder.flush()
        _ = float(jax.tree.leaves(center)[0].reshape(-1)[0])

    def _adopt_center() -> None:
        """Quiesce, then set the model's state to the center weights +
        consensus net/opt state."""
        _quiesce()
        model.params = center
        model.net_state = engine.mean_net_state()
        model.opt_state = engine.mean_opt_state()

    preempted = False
    i = 0
    while model.epoch < model.n_epochs:
        epoch = model.epoch
        recorder.start_epoch()
        if hasattr(data, "shuffle"):
            data.shuffle(epoch)
        for i in range(start_iter, data.n_batch_train):
            recorder.start()
            staged = (
                feed.next(i) if feed is not None
                else engine.put_batch(data.train_batch(i))
            )
            recorder.end("wait")

            if speeds is None:
                recorder.start()
                loss, err = engine.train_step_staged(
                    staged, model.current_lr
                )
                recorder.end("calc")
                # device scalars, materialized lazily (Recorder.flush)
                recorder.train_error(i, loss, err)

                step += 1
                if step % tau == 0:
                    n_exchanges += n_workers
                    recorder.start()
                    engine.params, center = exchange(engine.params, center)
                    # value-read fence (ClassifierModel.train_iter note)
                    _ = float(
                        jax.tree.leaves(center)[0].reshape(-1)[0]
                    )
                    recorder.end("comm")
            else:
                # out-of-step mode: each tick, worker w steps iff its
                # speed credit crosses 1; it exchanges when ITS OWN
                # step counter hits tau — different workers exchange
                # at different local step counts
                credit += speeds_arr
                mask = credit >= 1.0
                credit -= mask
                if not mask.any():
                    continue
                recorder.start()
                loss, err = engine.train_step_staged(
                    staged, model.current_lr,
                    step_mask=mask.astype(np.float32),
                )
                recorder.end("calc")
                recorder.train_error(i, loss, err)
                local_steps += mask
                since_exchange += mask
                exch = since_exchange >= tau
                if exch.any():
                    recorder.start()
                    engine.params, center = exchange_masked(
                        engine.params, center,
                        jnp.asarray(exch, jnp.float32),
                    )
                    _ = float(
                        jax.tree.leaves(center)[0].reshape(-1)[0]
                    )
                    recorder.end("comm")
                    since_exchange[exch] = 0
                    n_exchanges += int(exch.sum())
            recorder.print_train_info(i)
            _faults.maybe_inject_fault(epoch, i,
                                       checkpoint_dir=checkpoint_dir)
            _sup.heartbeat(recorder.n_iter, epoch, i,
                           resumed_from=resumed_from)
            if _sup.preemption_requested():
                preempted = True
                break
        start_iter = 0
        if preempted:
            break

        if data.n_batch_val:
            # server semantics: validate the CENTER weights
            _quiesce()
            l, e, e5 = engine.validate(
                data, params=center, net_state=engine.mean_net_state()
            )
            recorder.val_error(l, e, e5)

        recorder.end_epoch(epoch)
        model.adjust_hyperp(epoch + 1)
        if checkpoint_dir:
            # center owns the checkpoint (reference: server saves);
            # consensus momentum rides along so resume keeps velocity
            _adopt_center()
            model.save(checkpoint_dir, recorder)
        model.epoch += 1

    if feed is not None:
        feed.stop()
    _adopt_center()  # final/preempted weights = center + momentum

    if preempted:
        if checkpoint_dir:
            model.save(checkpoint_dir, recorder,
                       extra_meta={"next_iter": i + 1, "preempted": True})
        if verbose:
            print(
                f"preempted: checkpointed epoch {model.epoch} iter "
                f"{i + 1}, exiting cleanly", flush=True,
            )
        _sup.heartbeat(recorder.n_iter, model.epoch, i,
                       status="preempted")
    else:
        _sup.heartbeat(recorder.n_iter, model.epoch, None,
                       status="completed")
    _sup.uninstall_preemption_handler()

    last_val = recorder.val_records[-1] if recorder.val_records else {}
    out = {
        "epochs": model.epoch,
        "iterations": recorder.n_iter,
        "exchanges": n_exchanges,
        "final_train_loss": (
            recorder.train_losses[-1] if recorder.train_losses else None
        ),
        "final_val": last_val,
        "epoch_times": recorder.epoch_times,
        "preempted": preempted,
        "resumed_from": resumed_from,
        "restarts": recorder.restart_events,
        "n_restarts": len(recorder.restart_events),
        "mttr_s": recorder.mttr_s,
        "recorder": recorder,
        "model": model,
    }
    if speeds is not None:
        out["local_steps"] = local_steps.tolist()
    return out


def _run_distributed(
    *,
    modelfile: str,
    modelclass: str,
    config: dict,
    alpha: float | None,
    tau: int | None,
    n_epochs: int | None,
    checkpoint_dir: str | None,
    resume: bool,
    print_freq: int,
    verbose: bool,
    center_addr: str | None,
) -> dict:
    """Multi-process EASGD: each PROCESS is one worker over its local
    chips; process 0 additionally hosts the TCP center server.  No
    barrier anywhere in the training loop — each process trains and
    exchanges at its own pace (the reference's server/worker split,
    with DCN TCP replacing MPI Sendrecv)."""
    from theanompi_tpu.parallel import make_mesh
    from theanompi_tpu.parallel.center_server import (
        EASGDCenterClient,
        EASGDCenterServer,
    )

    pid = jax.process_index()
    local = jax.local_devices()
    mesh = make_mesh(data=len(local), devices=local)

    Model = _resolve_model(modelfile, modelclass)
    cfg = dict(config)
    if n_epochs is not None:
        cfg["n_epochs"] = n_epochs
    model = Model(cfg)
    model.build_model(n_replicas=len(local))
    model.compile_iter_fns(mesh=mesh)

    n_procs = jax.process_count()
    alpha = float(alpha if alpha is not None
                  else cfg.get("alpha", 1.0 / n_procs))
    tau = int(tau if tau is not None else cfg.get("tau", 4))
    _check_stability(alpha, n_procs, cfg.get("allow_unstable", False))

    recorder = Recorder(
        rank=pid, size=n_procs, print_freq=print_freq, verbose=verbose
    )
    # EVERY process loads (checkpoint_dir must be on a shared
    # filesystem, the standard pod setup) so all workers agree on the
    # restored epoch and start from the center weights
    start_iter, resumed_from = _sup.begin_resilient_run(
        model, recorder, checkpoint_dir, resume,
        verbose=verbose and pid == 0,
    )

    server = None
    if pid == 0:
        # bind all interfaces so remote hosts can reach the center;
        # the published address is this host's routable name
        host, port = ("0.0.0.0", 0)
        if center_addr:
            host, port = center_addr.rsplit(":", 1)
            port = int(port)
        server = EASGDCenterServer(
            model.params, alpha, host=host, port=port,
            n_workers=n_procs,
        )
        addr = f"{server.address[0]}:{server.address[1]}"
    if center_addr:
        addr = center_addr
    elif n_procs > 1:
        # share the (possibly ephemeral) port over the jax.distributed
        # KV store — same transport the coordinator bootstrap uses
        from jax._src import distributed as _dist

        client = _dist.global_state.client
        if pid == 0:
            client.key_value_set("tm_easgd_center", addr)
        else:
            addr = client.blocking_key_value_get("tm_easgd_center", 60000)
    # the strategy knob's wire dtype applies to the TCP exchange too
    # (the reference's asa16/nccl16 fp16 wire, SURVEY §5.8): *16
    # configs ship bf16 leaves both ways, elastic math stays fp32.
    # exch_compression supersedes it with the int8/fp8 per-leaf
    # quantized codec (4x) — the worker carries a push-leg EF residual
    # inside the client so its time-averaged contribution to the
    # center stays unbiased.
    from theanompi_tpu.parallel import ExchangePlan

    exchange = ExchangePlan.from_config(cfg)
    tcp = EASGDCenterClient(
        (addr.rsplit(":", 1)[0], int(addr.rsplit(":", 1)[1])),
        wire=exchange.wire, error_feedback=exchange.error_feedback,
    )

    data = model.data
    if verbose and pid == 0:
        print(
            f"EASGD(distributed): {n_procs} worker processes x "
            f"{len(local)} chips, alpha={alpha:.4f} tau={tau}",
            flush=True,
        )

    step = 0
    n_exchanges = 0
    preempted = False
    center_vals: list[dict] = []
    center_stats: dict | None = None
    while model.epoch < model.n_epochs:
        epoch = model.epoch
        recorder.start_epoch()
        if hasattr(data, "shuffle"):
            data.shuffle(epoch + pid * 7919)  # decorrelate worker data
        for i in range(start_iter, data.n_batch_train):
            model.train_iter(i, recorder)
            step += 1
            if step % tau == 0:
                recorder.flush()  # fence local step before reading params
                recorder.start()
                host_params = jax.device_get(model.params)
                new_params = tcp.exchange(host_params, alpha)
                model.params = jax.device_put(
                    new_params, jax.tree.map(lambda x: x.sharding,
                                             model.params),
                )
                recorder.end("comm")
                n_exchanges += 1
            recorder.print_train_info(i)
            _faults.maybe_inject_fault(epoch, i,
                                       checkpoint_dir=checkpoint_dir)
            _sup.heartbeat(recorder.n_iter, epoch, i,
                           resumed_from=resumed_from)
            if _sup.preemption_requested():
                preempted = True
                break
        start_iter = 0
        if preempted:
            # drain gracefully through the normal teardown: announce
            # stop to the center, let it checkpoint the center weights
            # (with next_iter so the relaunch continues mid-epoch)
            break

        if data.n_batch_val:
            vals = [model.val_iter(j, recorder)
                    for j in range(data.n_batch_val)]
            l, e, e5 = (float(sum(v) / len(v)) for v in zip(*vals))
            recorder.val_error(l, e, e5)
        if data.n_batch_val and server is not None:
            # the reference's server validates the CENTER (SURVEY
            # §3.2) — local-val above measures each worker's replica,
            # this measures the consensus weights users actually ship.
            # Process 0 holds the center in-process; no TCP round-trip
            local_params = model.params
            model.params = jax.device_put(
                server.center_tree(),
                jax.tree.map(lambda x: x.sharding, local_params),
            )
            # throwaway recorder: the center sweep is process-0-only
            # bookkeeping — folding its wall time into the shared
            # recorder would inflate process 0's epoch/val timings
            # relative to the other workers (ADVICE r3)
            center_rec = Recorder(verbose=False)
            cvals = [model.val_iter(j, center_rec)
                     for j in range(data.n_batch_val)]
            cl, ce, ce5 = (float(sum(v) / len(v)) for v in zip(*cvals))
            model.params = local_params
            center_vals.append(
                {"epoch": epoch, "loss": cl, "err": ce, "err5": ce5}
            )
            if verbose:
                print(
                    f"EASGD center val: epoch {epoch} "
                    f"loss {cl:.4f} err {ce:.4f}",
                    flush=True,
                )
        recorder.end_epoch(epoch)
        model.adjust_hyperp(epoch + 1)
        if server is not None and checkpoint_dir:
            # per-epoch crash recovery, like the single-host path: the
            # CENTER is the authoritative weights — stash the local
            # replica, save the center snapshot, restore, train on
            local_params = model.params
            model.params = jax.device_put(
                server.center_tree(),
                jax.tree.map(lambda x: x.sharding, model.params),
            )
            model.save(checkpoint_dir, recorder)
            model.params = local_params
        model.epoch += 1

    # every worker (incl. process 0) announces completion; process 0
    # keeps the server alive until ALL workers have — exiting earlier
    # would kill slower workers' pending exchanges mid-run
    tcp.close()
    if server is not None:
        # TM_EASGD_STOP_TIMEOUT_S: how long the center waits for every
        # worker's 'stop' before tearing down anyway — the bound on how
        # long a DEAD worker can hold the shutdown (fault drills set it
        # low; production default tolerates slow epochs)
        stop_timeout = float(
            os.environ.get("TM_EASGD_STOP_TIMEOUT_S", "600")
        )
        if not server.wait_all_stopped(timeout=stop_timeout) and verbose:
            print(
                "EASGD center: timed out waiting for all workers to "
                "stop; shutting down anyway",
                flush=True,
            )
        # center owns the final weights + checkpoint (server semantics)
        center = server.center_tree()
        model.params = jax.device_put(
            center, jax.tree.map(lambda x: x.sharding, model.params)
        )
        if checkpoint_dir:
            model.save(
                checkpoint_dir, recorder,
                extra_meta=(
                    {"next_iter": i + 1, "preempted": True}
                    if preempted else None
                ),
            )
        center_stats = server.stats()
        if verbose:
            print(
                f"EASGD center: {center_stats['exchanges']} exchanges, "
                f"mean wait {center_stats['mean_wait_s'] * 1e3:.1f}ms "
                f"(max {center_stats['max_wait_s'] * 1e3:.1f}ms), "
                f"mean hold {center_stats['mean_hold_s'] * 1e3:.1f}ms",
                flush=True,
            )
        server.stop()

    _sup.heartbeat(
        recorder.n_iter, model.epoch, None,
        status="preempted" if preempted else "completed",
    )
    _sup.uninstall_preemption_handler()
    if hasattr(model, "close_feed"):
        model.close_feed()  # park the streaming feed's producer thread
    last_val = recorder.val_records[-1] if recorder.val_records else {}
    return {
        "epochs": model.epoch,
        "iterations": recorder.n_iter,
        "exchanges": n_exchanges,
        "preempted": preempted,
        "resumed_from": resumed_from,
        "restarts": recorder.restart_events,
        "n_restarts": len(recorder.restart_events),
        "mttr_s": recorder.mttr_s,
        "process_index": pid,
        "final_train_loss": (
            recorder.train_losses[-1] if recorder.train_losses else None
        ),
        "final_val": last_val,
        # per-epoch validation of the CENTER weights (process 0 only;
        # empty elsewhere) — the server-semantics metric
        "center_vals": center_vals,
        "center_val": center_vals[-1] if center_vals else None,
        # server backpressure snapshot (process 0 only): queue wait /
        # lock hold per exchange — the single-center scaling signal
        "center_stats": center_stats,
        "epoch_times": recorder.epoch_times,
        "recorder": recorder,
        "model": model,
    }


if __name__ == "__main__":
    _launcher.worker_main(run)
