"""GoSGD: asynchronous gossip SGD (Blot et al. 2016).

Reference: ``theanompi/gosgd_worker.py`` — every iteration each worker
trains locally, then with probability ``p`` picks a random peer and
``isend``s ``(params, score/2)`` to it, halving its own score; on
receive, the peer merges parameters weighted by scores and adds the
scores (SURVEY §3.3).

TPU-native shape: workers are per-device replicas with a stacked
sharded worker axis (``ReplicaEngine``); one gossip round is a single
jitted score-weighted routing contraction
(``parallel.exchange.gossip_matrix_round``) whose Bernoulli push mask
and random destinations are host-sampled *runtime arrays* — the random
draw changes every round without recompiling, and XLA lowers the
delivery to one cross-device reduce over ICI instead of point-to-point
MPI messages.

Validation runs per-replica (each worker scores its own shard of the
val set — exactly what the reference's N processes reported), and the
checkpoint takes the highest-score worker's weights (the reference
took any worker's).  Score-weighted *averaging* of replicas is
deliberately NOT used as the final model: under sparse gossip the
replicas are independently-trained networks whose parameter average is
meaningless (permutation symmetry), and measuring it oscillates
between degenerate one-class predictors.
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
from theanompi_tpu.parallel import gossip_matrix_round
from theanompi_tpu.utils import Recorder, faults as _faults
from theanompi_tpu.utils import supervisor as _sup
from theanompi_tpu.workers.bsp_worker import _build_mesh, _resolve_model
from theanompi_tpu.workers.replica_engine import ReplicaEngine


def _adopt_best(model, engine, scores) -> None:
    """Copy the highest-score worker's replica into the model slot
    (reference semantics: any worker's weights are the model; the top
    score has absorbed the most gossip mass)."""
    k = int(jnp.argmax(scores))

    def take(tree):
        return jax.tree.map(lambda x: x[k], tree)

    model.params = take(engine.params)
    model.net_state = take(engine.net_state)
    model.opt_state = take(engine.opt_state)


def run(
    devices: Sequence[Any] | None = None,
    modelfile: str = "",
    modelclass: str = "",
    *,
    config: dict | None = None,
    push_prob: float | None = None,
    staleness: int | None = None,
    n_epochs: int | None = None,
    checkpoint_dir: str | None = None,
    resume: bool = False,
    print_freq: int = 40,
    verbose: bool = True,
    seed: int | None = None,
    **extra: Any,
) -> dict:
    """Train ``modelclass`` under GoSGD; returns a summary dict.

    ``push_prob`` — per-worker per-iteration Bernoulli push probability
    (the reference's ``p``; its IMDB LSTM demo used small p).

    ``staleness`` — rounds a pushed message spends "in flight" before
    the receiver merges it (0 = same-round delivery).  The reference's
    isend/probe pair delivered whenever the receiver polled — pushes
    arrived stale while both peers kept training; this knob reproduces
    that staleness deterministically (sender still halves its score at
    send time)."""
    import jax as _jax

    if _jax.process_count() > 1:
        if staleness not in (None, 0):
            raise ValueError(
                "staleness= is a single-controller knob (deterministic "
                "delayed delivery); in multi-process mode arrivals are "
                "as stale as the wire made them — drop the argument"
            )
        return _run_distributed(
            modelfile=modelfile,
            modelclass=modelclass,
            config={**(config or {}), **extra},
            push_prob=push_prob,
            n_epochs=n_epochs,
            checkpoint_dir=checkpoint_dir,
            resume=resume,
            print_freq=print_freq,
            verbose=verbose,
            seed=seed,
        )
    mesh = _build_mesh(devices)
    n_workers = mesh.shape["data"]
    if n_workers < 2:
        raise ValueError(
            "GoSGD needs >= 2 workers (devices) to gossip between; "
            f"got {n_workers}. Use BSP for single-device training."
        )

    Model = _resolve_model(modelfile, modelclass)
    cfg = dict(config or {})
    cfg.update(extra)
    if n_epochs is not None:
        cfg["n_epochs"] = n_epochs
    model = Model(cfg)
    model.build_model(n_replicas=n_workers)

    p_push = float(
        push_prob if push_prob is not None else cfg.get("push_prob", 0.25)
    )
    delay = int(
        staleness if staleness is not None else cfg.get("staleness", 0)
    )
    if delay < 0:
        raise ValueError(f"staleness must be >= 0, got {delay}")

    recorder = Recorder(
        rank=0, size=n_workers, print_freq=print_freq, verbose=verbose
    )
    # mid-epoch resumes restart every replica from the adopted
    # best-score checkpoint; scores re-level from uniform
    start_iter, resumed_from = _sup.begin_resilient_run(
        model, recorder, checkpoint_dir, resume, verbose=verbose
    )

    # ReplicaEngine stacks model.params — already the restored
    # consensus weights on resume, so no re-broadcast is needed.
    engine = ReplicaEngine(model, mesh)
    # each worker starts with score 1/W (reference: scores sum to 1)
    scores = jax.device_put(
        jnp.full((n_workers,), 1.0 / n_workers, jnp.float32),
        engine.replicated,
    )

    gossip = jax.jit(gossip_matrix_round, donate_argnums=(0,))
    if delay:
        from collections import deque

        from theanompi_tpu.parallel.exchange import (
            gossip_deliver,
            gossip_send,
        )

        send = jax.jit(gossip_send)
        deliver = jax.jit(gossip_deliver, donate_argnums=(0,))
        in_flight: "deque" = deque()  # (routing, params+opt snapshot)

        def drain(scores):
            """Deliver every payload still in flight (FIFO).  Senders
            already halved their scores at send time, so an undelivered
            payload is lost score mass — scores would no longer sum to
            1 and _adopt_best would mis-weight; quiesce the wire before
            any adopt/checkpoint (the reference's MPI analogue:
            completing outstanding isends before a barrier)."""
            while in_flight:
                routing_d, snap_d = in_flight.popleft()
                merged, scores = deliver(
                    {"params": engine.params, "opt": engine.opt_state},
                    scores, snap_d, routing_d,
                )
                engine.params = merged["params"]
                engine.opt_state = merged["opt"]
            return scores
    else:
        def drain(scores):
            return scores
    host_rng = np.random.default_rng(
        seed if seed is not None else model.seed + 101
    )

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
            f"GoSGD: {n_workers} workers, p={p_push}, "
            f"{data.n_batch_train} train batches x {data.global_batch} "
            f"global batch",
            flush=True,
        )

    n_rounds = 0
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

            recorder.start()
            loss, err = engine.train_step_staged(staged, model.current_lr)
            recorder.end("calc")
            # device scalars, materialized lazily (Recorder.flush)
            recorder.train_error(i, loss, err)

            # host-sampled gossip round (reference: Bernoulli(p) isend
            # to a uniform random peer != self)
            push = host_rng.random(n_workers) < p_push
            if push.any():
                recorder.start()
                route = host_rng.integers(0, n_workers - 1, n_workers)
                route += route >= np.arange(n_workers)  # peer != self
                # momentum travels with the params: merging weights but
                # keeping each worker's stale velocity makes the
                # consensus oscillate (momentum then points away from
                # the merged point), so the whole (params, opt) pair is
                # averaged with the same scores.
                if not delay:
                    merged, scores = gossip(
                        {"params": engine.params, "opt": engine.opt_state},
                        scores,
                        jnp.asarray(route, jnp.int32),
                        jnp.asarray(push, jnp.float32),
                    )
                    engine.params = merged["params"]
                    engine.opt_state = merged["opt"]
                    _ = float(scores[0])  # value-read fence
                else:
                    # stale delivery: score halves now, payload rides
                    # in flight for `delay` rounds
                    scores, routing = send(
                        scores,
                        jnp.asarray(route, jnp.int32),
                        jnp.asarray(push, jnp.float32),
                    )
                    # deep-copy the snapshot: the next train step
                    # DONATES engine.params/opt_state, which would
                    # invalidate a bare reference held in the queue.
                    # Quiesce first: dispatching the copy programs
                    # while the train step's or ``send``'s collectives
                    # are still running can starve XLA:CPU's rendezvous
                    # on low-core hosts (observed: 4/8 threads arrive,
                    # 40s termination timeout, hard abort).  Value-read
                    # of BOTH pending outputs is the fence.
                    _ = float(loss)
                    _ = float(scores[0])
                    snap = jax.tree.map(
                        jnp.copy,
                        {"params": engine.params, "opt": engine.opt_state},
                    )
                    # the rendezvous-starvation hazard is specific to
                    # XLA:CPU low-core hosts, so only there is EVERY
                    # copy program fenced (one per leaf); on real
                    # chips programs execute in dispatch order and one
                    # read bounds the queue without serializing
                    # hundreds of D2H round-trips
                    leaves = jax.tree.leaves(snap)
                    if jax.default_backend() == "cpu":
                        for leaf in leaves:
                            _ = float(leaf.ravel()[0])
                    else:
                        _ = float(leaves[-1].ravel()[0])
                    in_flight.append((routing, snap))
                recorder.end("comm")
                n_rounds += 1
            if delay and len(in_flight) > delay:
                recorder.start()
                routing_d, snap_d = in_flight.popleft()
                merged, scores = deliver(
                    {"params": engine.params, "opt": engine.opt_state},
                    scores, snap_d, routing_d,
                )
                engine.params = merged["params"]
                engine.opt_state = merged["opt"]
                _ = float(scores[0])
                recorder.end("comm")
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
            # per-replica validation (reference: each process reports
            # on its own shard of the val set).  Flush first: any
            # multi-device dispatch racing the unfenced last train
            # scan can starve XLA:CPU's rendezvous on low-core hosts
            recorder.flush()
            l, e, e5 = engine.validate(data)
            recorder.val_error(l, e, e5)

        # end_epoch flushes pending metrics — the train scan is fenced
        # past this point; drain/_adopt_best read score VALUES, fencing
        # the gossip programs they race
        recorder.end_epoch(epoch)
        model.adjust_hyperp(epoch + 1)
        if checkpoint_dir:
            scores = drain(scores)
            _adopt_best(model, engine, scores)
            model.save(checkpoint_dir, recorder)
        model.epoch += 1

    if feed is not None:
        feed.stop()
    scores = drain(scores)
    _adopt_best(model, engine, scores)

    if preempted:
        if checkpoint_dir:
            recorder.flush()
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
    return {
        "epochs": model.epoch,
        "iterations": recorder.n_iter,
        "gossip_rounds": n_rounds,
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


# advances once per _run_distributed call, in lockstep across the
# processes of a distributed session (they all call run() the same
# number of times in a sweep) — isolates each run's KV keys
_DIST_RUN_COUNTER = 0


def _run_distributed(
    *,
    modelfile: str,
    modelclass: str,
    config: dict,
    push_prob: float | None,
    n_epochs: int | None,
    checkpoint_dir: str | None,
    resume: bool,
    print_freq: int,
    verbose: bool,
    seed: int | None,
) -> dict:
    """Multi-process GoSGD: each PROCESS is one gossip worker over its
    local chips (reference: one worker per MPI rank).  Pushes are
    fire-and-forget TCP sends to a random peer (``gossip_net`` — the
    isend analogue); each iteration the worker polls its inbox and
    merges whatever arrived, score-weighted.  No barrier anywhere in
    training: arrivals are as stale as the wire made them, exactly the
    reference's asynchrony."""
    from jax._src import distributed as _dist

    from theanompi_tpu.parallel import make_mesh
    from theanompi_tpu.parallel.gossip_net import GossipPeer

    pid = jax.process_index()
    n_procs = jax.process_count()
    local = jax.local_devices()
    mesh = make_mesh(data=len(local), devices=local)

    Model = _resolve_model(modelfile, modelclass)
    cfg = dict(config)
    if n_epochs is not None:
        cfg["n_epochs"] = n_epochs
    model = Model(cfg)
    model.build_model(n_replicas=len(local))
    model.compile_iter_fns(mesh=mesh)

    p_push = float(
        push_prob if push_prob is not None else cfg.get("push_prob", 0.25)
    )
    # *16 strategies put bf16 on the gossip wire (halves push bytes
    # AND outbox memory); the score-weighted merge stays fp32.
    # exch_compression supersedes it: int8/fp8 per-leaf quantized
    # pushes (4x smaller payloads AND outbox).  No EF residual here —
    # a gossip push's receiver set is random and unacknowledged, so
    # there is no single counterpart whose view a residual could
    # unbias; the score-weighted merge dilutes the per-push rounding
    # instead.
    from theanompi_tpu.parallel import ExchangePlan

    wire = ExchangePlan.from_config(cfg).wire
    recorder = Recorder(
        rank=pid, size=n_procs, print_freq=print_freq, verbose=verbose
    )
    # shared filesystem (standard pod setup): everyone restarts from
    # the adopted-best weights of the previous run
    start_iter, resumed_from = _sup.begin_resilient_run(
        model, recorder, checkpoint_dir, resume,
        verbose=verbose and pid == 0,
    )

    # peer bootstrap over the jax.distributed KV store.  The nonce
    # makes repeat run() calls in one distributed session (parameter
    # sweeps) use fresh keys — every process's counter advances in
    # lockstep since they all call run() the same number of times.
    global _DIST_RUN_COUNTER
    _DIST_RUN_COUNTER += 1
    tag = f"{os.environ.get('TM_RUN_ID', '0')}_{_DIST_RUN_COUNTER}"
    peer = GossipPeer()
    kv = _dist.global_state.client
    kv.key_value_set(f"tm_gosgd_{tag}_peer_{pid}",
                     f"{peer.address[0]}:{peer.address[1]}")
    peers: dict[int, tuple[str, int]] = {}
    for r in range(n_procs):
        if r == pid:
            continue
        a = kv.blocking_key_value_get(f"tm_gosgd_{tag}_peer_{r}", 60000)
        host, port = a.rsplit(":", 1)
        peers[r] = (host, int(port))

    # score-weighted merge of an arriving snapshot into the local pair
    # (a is a RUNTIME scalar: merge weights change every delivery and
    # must not retrace)
    @partial(jax.jit, donate_argnums=(0,))
    def merge(mine, theirs, a):
        return jax.tree.map(
            lambda x, y: (a * x.astype(jnp.float32)
                          + (1.0 - a) * y.astype(jnp.float32)).astype(x.dtype),
            mine, theirs,
        )

    def snapshot_host():
        return jax.tree.map(
            lambda x: np.asarray(x),
            {"params": model.params, "opt": model.opt_state},
        )

    host_rng = np.random.default_rng(
        (seed if seed is not None else model.seed + 211) + pid * 7919
    )
    score = 1.0 / n_procs
    n_pushes = 0
    n_merges = 0
    mid_saves: list[dict] = []
    epoch_scores: list[float] = []
    data = model.data
    if verbose and pid == 0:
        print(
            f"GoSGD(distributed): {n_procs} worker processes x "
            f"{len(local)} chips, p={p_push}",
            flush=True,
        )

    def drain_inbox(score):
        nonlocal n_merges
        # reclaim score mass from pushes the wire gave up on (dropped
        # oldest under backpressure / dead peer) — conservation first
        score += peer.take_refunds()
        for s_in, leaves in peer.poll():
            theirs = jax.tree.unflatten(
                jax.tree.structure(
                    {"params": model.params, "opt": model.opt_state}
                ),
                leaves,
            )
            a = score / (score + s_in)
            merged = merge(
                {"params": model.params, "opt": model.opt_state},
                theirs, jnp.float32(a),
            )
            model.params = merged["params"]
            model.opt_state = merged["opt"]
            score += s_in
            n_merges += 1
        return score

    preempted = False
    while model.epoch < model.n_epochs:
        epoch = model.epoch
        recorder.start_epoch()
        if hasattr(data, "shuffle"):
            data.shuffle(epoch + pid * 104729)  # decorrelate worker data
        for i in range(start_iter, data.n_batch_train):
            model.train_iter(i, recorder)
            # probe-and-merge whatever the wire delivered (reference:
            # per-iteration MPI probe loop)
            recorder.start()
            score = drain_inbox(score)
            if host_rng.random() < p_push:
                dst = int(host_rng.integers(0, n_procs - 1))
                dst += dst >= pid  # peer != self
                recorder.flush()  # fence: snapshot AFTER the step
                snap = snapshot_host()
                score *= 0.5
                peer.push(peers[dst], score, jax.tree.leaves(snap),
                          wire=wire)
                n_pushes += 1
            recorder.end("comm")
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
            # fall through to the quiesce path: queued pushes ship,
            # score mass is conserved, the best scorer checkpoints
            break

        if data.n_batch_val:
            vals = [model.val_iter(j, recorder)
                    for j in range(data.n_batch_val)]
            l, e, e5 = (float(sum(v) / len(v)) for v in zip(*vals))
            recorder.val_error(l, e, e5)
        recorder.end_epoch(epoch)
        model.adjust_hyperp(epoch + 1)
        epoch_scores.append(float(score))
        if checkpoint_dir:
            # mid-run BEST-SCORE checkpoint (VERDICT r2 item 10): each
            # worker publishes its post-epoch score to the KV store,
            # then reads the peers' — everyone publishes before
            # reading, so all complete views agree on the argmax and
            # exactly the best worker saves.  NOTE: checkpoint_dir
            # thus implies a per-epoch soft sync bounded by
            # TM_GOSGD_CKPT_SYNC_S (default 60s) per missing peer;
            # without checkpointing the training loop stays
            # barrier-free.  The final checkpoint below still uses
            # the exact post-drain scores.
            import json as _json2

            kv.key_value_set(
                f"tm_gosgd_{tag}_esc_{epoch}_{pid}", f"{score:.9e}"
            )
            # compare the PUBLISHED representation on both sides —
            # comparing a peer's rounded wire value against the local
            # exact float can make two workers each defer to (or each
            # outrank) the other when scores differ below the wire
            # precision, yielding zero or two savers
            best_pid = pid
            best_score = float(f"{score:.9e}")
            complete_view = True
            sync_ms = int(float(os.environ.get(
                "TM_GOSGD_CKPT_SYNC_S", "60"
            )) * 1000)
            for r in range(n_procs):
                if r == pid:
                    continue
                try:
                    s = float(kv.blocking_key_value_get(
                        f"tm_gosgd_{tag}_esc_{epoch}_{r}", sync_ms
                    ))
                except Exception:
                    # a worker with an INCOMPLETE view must not elect
                    # itself: its argmax can disagree with a complete
                    # view's, and two model.save() writers would
                    # interleave shards.  Skipping one epoch's
                    # mid-run save is benign — the next epoch retries
                    # and the final checkpoint uses exact scores.
                    complete_view = False
                    continue
                if s > best_score or (s == best_score and r < best_pid):
                    best_pid, best_score = r, s
            if not complete_view:
                # operator-visible: a timed-out peer read means NOBODY
                # may save this epoch (the best-scorer might be among
                # those who saw an incomplete view too) — log it so a
                # silent run of skipped mid-run saves is diagnosable
                # (ADVICE r3)
                print(
                    f"[gosgd {pid}] epoch {epoch}: peer score read "
                    f"timed out; skipping mid-run checkpoint election "
                    f"(next epoch retries)",
                    flush=True,
                )
            if complete_view and best_pid == pid:
                model.save(checkpoint_dir, recorder)
                with open(os.path.join(
                    checkpoint_dir, "gosgd_best.json"
                ), "w") as f:
                    _json2.dump({"epoch": epoch, "pid": pid,
                                 "score": score}, f)
                mid_saves.append({"epoch": epoch, "score": score})
        model.epoch += 1

    # quiesce: ship queued pushes, publish per-destination DELIVERED
    # counts (what actually left this host — a queued-then-dropped
    # payload must not be awaited), then every process drains its
    # inbox until it has received exactly what was addressed to it —
    # a receive-side ack, so no score mass is abandoned on the wire
    # (flush() only guarantees the bytes LEFT the sender).  The KV
    # waits scale with the run: the no-barrier design means worker
    # skew grows with training length (TM_GOSGD_QUIESCE_S overrides).
    import json as _json
    import time as _time

    wall = sum(recorder.epoch_times) or 60.0
    quiesce_s = float(os.environ.get(
        "TM_GOSGD_QUIESCE_S", max(600.0, 2.0 * wall)
    ))
    kv_ms = int(quiesce_s * 1000)
    if not peer.flush(timeout=quiesce_s):
        # the wire gave up: reclaim the queued payloads' score mass
        # BEFORE publishing, so sent_counts is the exact total and the
        # mass is in our posted score rather than lost
        peer.cancel_pending()
        if verbose:
            print("GoSGD quiesce: flush timed out; pending pushes "
                  "cancelled and refunded", flush=True)
    delivered = {
        r: peer.sent_counts.get(addr, 0) for r, addr in peers.items()
    }
    kv.key_value_set(f"tm_gosgd_{tag}_sent_{pid}",
                     _json.dumps({str(r): c for r, c in delivered.items()}))
    expected = 0
    for r in range(n_procs):
        if r == pid:
            continue
        counts = _json.loads(
            kv.blocking_key_value_get(f"tm_gosgd_{tag}_sent_{r}", kv_ms)
        )
        expected += int(counts.get(str(pid), 0))
    deadline = _time.monotonic() + quiesce_s
    score = drain_inbox(score)  # also reclaims refunded mass
    while n_merges < expected and _time.monotonic() < deadline:
        _time.sleep(0.05)
        score = drain_inbox(score)
    if n_merges < expected and verbose:
        print(
            f"GoSGD quiesce: received {n_merges}/{expected} pushes "
            f"before timeout",
            flush=True,
        )

    kv.key_value_set(f"tm_gosgd_{tag}_done_{pid}", f"{score:.9e}")
    final_scores = {}
    for r in range(n_procs):
        final_scores[r] = float(
            kv.blocking_key_value_get(f"tm_gosgd_{tag}_done_{r}", kv_ms)
        )

    if checkpoint_dir:
        # reference semantics: the best worker's weights are the model;
        # the highest post-drain score saves the final checkpoint
        best = max(final_scores, key=lambda r: final_scores[r])
        if pid == best:
            model.save(
                checkpoint_dir, recorder,
                extra_meta=(
                    {"next_iter": i + 1, "preempted": True}
                    if preempted else None
                ),
            )
    peer.close()

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
        "preempted": preempted,
        "resumed_from": resumed_from,
        "restarts": recorder.restart_events,
        "n_restarts": len(recorder.restart_events),
        "mttr_s": recorder.mttr_s,
        "pushes": n_pushes,
        "delivered": sum(delivered.values()),
        "merges": n_merges,
        "score": score,
        # epochs where THIS process held the best published score and
        # wrote the mid-run checkpoint (VERDICT r2 item 10)
        "mid_saves": mid_saves,
        "epoch_scores": epoch_scores,
        "process_index": pid,
        "final_train_loss": (
            recorder.train_losses[-1] if recorder.train_losses else None
        ),
        "final_val": last_val,
        "epoch_times": recorder.epoch_times,
        "recorder": recorder,
        "model": model,
    }


if __name__ == "__main__":
    _launcher.worker_main(run)
