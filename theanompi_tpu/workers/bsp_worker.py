"""BSP worker: synchronous data-parallel training loop.

Reference: ``theanompi/bsp_worker.py`` — ``BSP_Worker``: per-process
loop of ``train_iter`` → ``exchanger.exchange`` every iteration →
periodic validation → lr schedule → checkpoint (SURVEY §3.1).

TPU-native shape: ONE controller process drives all chips through a
``Mesh``; the exchange lives *inside* the jitted train step (gradient
allreduce), so the loop body is just ``model.train_iter`` — XLA
overlaps the collective with backprop, which the reference could not.
"""

from __future__ import annotations

import importlib
import os
from typing import Any, Sequence

from theanompi_tpu import launcher as _launcher
from theanompi_tpu.obs.memory import (
    FIRST_FENCE,
    SUMMARY,
    begin_memory_account,
)
from theanompi_tpu.obs.setup import begin_setup
from theanompi_tpu.parallel import (
    ExchangePlan,
    default_devices,
    dp_replicas,
    make_mesh,
)
from theanompi_tpu.utils import Recorder, faults as _faults
from theanompi_tpu.utils import supervisor as _sup


def _resolve_model(modelfile: str, modelclass: str):
    mod = importlib.import_module(modelfile)
    return getattr(mod, modelclass)


ELASTIC_BATCH_POLICIES = ("global", "per_replica")


def _peek_resume_meta(cfg: dict, checkpoint_dir: str) -> dict:
    """Metadata of the checkpoint the resume will ACTUALLY load —
    validated with the same setting ``model.load`` uses, so a corrupt
    newest checkpoint (quarantined here, exactly as load() would)
    cannot make the elastic batch/LR policy read a different world
    than the one the restore falls back to."""
    from theanompi_tpu.utils.checkpoint import (
        checkpoint_meta,
        latest_checkpoint,
    )

    path = latest_checkpoint(
        checkpoint_dir,
        validate=bool(cfg.get("validate_checkpoint", True)),
    )
    return checkpoint_meta(path) if path is not None else {}


def _elastic_trim_devices(devices, cfg: dict, checkpoint_dir: str,
                          verbose: bool):
    """Fit the elastic world to the batch constraint BEFORE the mesh
    builds: under the ``"global"`` policy the saved global batch must
    divide the new replica count, so after e.g. ``lose_device``
    (8 → 7) the run continues at the LARGEST width that divides it
    (7 → dp=4, idling 3 devices) instead of crash-looping on the
    divisibility refusal — the resize-the-world contract."""
    if str(cfg.get("elastic_batch_policy", "global")) != "global":
        return devices
    meta = _peek_resume_meta(cfg, checkpoint_dir)
    saved_global = meta.get("global_batch")
    if saved_global is None and meta.get("world_size") \
            and cfg.get("batch_size") is not None:
        saved_global = int(meta["world_size"]) * int(cfg["batch_size"])
    if not saved_global:
        return devices
    prod = 1
    for k in ("tp", "sp", "pp", "ep"):
        prod *= int(cfg.get(k, 1))
    n_avail = len(devices) if devices is not None \
        else len(default_devices())
    dp_avail = n_avail // prod
    if dp_avail < 1 or saved_global % dp_avail == 0:
        return devices
    dp_fit = next(
        d for d in range(dp_avail, 0, -1) if saved_global % d == 0
    )
    n_use = dp_fit * prod
    if verbose:
        print(
            f"elastic resume: global batch {saved_global} does not "
            f"divide over {dp_avail} replicas — using {n_use} of "
            f"{n_avail} available devices (dp={dp_fit})",
            flush=True,
        )
    return (
        list(devices)[:n_use] if devices is not None
        else list(range(n_use))
    )


def _apply_elastic_policy(
    cfg: dict, n_replicas: int, checkpoint_dir: str, verbose: bool
) -> dict | None:
    """Elastic resume across a world change: peek the newest
    checkpoint's world stamp and rescale the batch/LR per
    ``elastic_batch_policy`` BEFORE the model builds its pipeline.

    - ``"global"`` (default): keep the GLOBAL batch — the per-replica
      batch grows/shrinks by old_world/new_world, so the optimization
      trajectory matches an uninterrupted equal-batch run (the batch
      schedule is the same permutation slices; only the reduction
      sharding changes).  Needs the global batch to divide the new
      replica count.
    - ``"per_replica"``: keep the per-replica batch — the global
      batch scales with the world, and the LR linear-scales with it
      (Goyal et al. 2017): ``lr *= new/old``, applied to ``lr`` and
      any dict ``lr_schedule`` entries present in the config.

    Returns a summary note (or None when no world change applies)."""
    policy = str(cfg.get("elastic_batch_policy", "global"))
    if policy not in ELASTIC_BATCH_POLICIES:
        raise ValueError(
            f"elastic_batch_policy must be one of "
            f"{ELASTIC_BATCH_POLICIES} ('global' keeps the global "
            f"batch by growing the per-replica batch; 'per_replica' "
            f"keeps the per-replica batch and linear-scales the LR), "
            f"got {policy!r}"
        )
    meta = _peek_resume_meta(cfg, checkpoint_dir)
    saved_world = meta.get("world_size")
    if not saved_world or int(saved_world) == n_replicas:
        return None
    saved_world = int(saved_world)
    saved_global = meta.get("global_batch")
    if saved_global is None and cfg.get("batch_size") is not None:
        saved_global = saved_world * int(cfg["batch_size"])
    note = {
        "policy": policy,
        "saved_world": saved_world,
        "saved_global": saved_global,
    }
    if policy == "global":
        if saved_global is None:
            raise ValueError(
                "elastic_batch_policy='global' needs the checkpoint's "
                "global_batch stamp (pre-elastic checkpoint) or an "
                "explicit batch_size in the config"
            )
        if saved_global % n_replicas:
            raise ValueError(
                f"elastic_batch_policy='global': global batch "
                f"{saved_global} does not divide over the new world "
                f"of {n_replicas} replicas — resume at a width that "
                f"divides it, or use elastic_batch_policy="
                f"'per_replica'"
            )
        cfg["batch_size"] = saved_global // n_replicas
        note["batch_size"] = cfg["batch_size"]
    else:
        scale = n_replicas / float(saved_world)
        if "lr" in cfg:
            cfg["lr"] = float(cfg["lr"]) * scale
        sched = cfg.get("lr_schedule")
        if isinstance(sched, dict):
            cfg["lr_schedule"] = {
                k: float(v) * scale for k, v in sched.items()
            }
        note["lr_scale"] = scale
    if verbose:
        print(
            f"elastic resume: world {saved_world} -> {n_replicas}, "
            f"policy={policy} ({note})",
            flush=True,
        )
    return note


def _build_mesh(devices: Sequence[Any] | None, config: dict | None = None):
    """Mesh for the BSP run: remaining devices become the data axis
    after the model's parallelism knobs (``tp/sp/pp/ep`` config keys,
    the Llama-family convention) claim theirs — so
    ``BSP().init(modelfile=...llama...)`` drives model-parallel
    layouts through the same rule surface as plain DP."""
    devs = default_devices()
    if devices is not None:
        n = len(devices)
        if n > len(devs):
            raise ValueError(f"requested {n} devices, have {len(devs)}")
        devs = devs[:n]
    c = config or {}
    tp, sp, pp, ep = (
        int(c.get(k, 1)) for k in ("tp", "sp", "pp", "ep")
    )
    prod = tp * sp * pp * ep
    if len(devs) < prod:
        raise ValueError(
            f"tp*sp*pp*ep={prod} needs at least {prod} devices, "
            f"got {len(devs)}"
        )
    if len(devs) % prod:
        raise ValueError(
            f"tp*sp*pp*ep={prod} must divide the {len(devs)} requested "
            f"devices — a floor division would silently idle "
            f"{len(devs) % prod} of them"
        )
    return make_mesh(
        data=len(devs) // prod,
        model=tp, seq=sp, pipe=pp, expert=ep,
        devices=devs,
    )


def _profile_step_phase(model, n_devices: int, verbose: bool) -> dict:
    """One profiled training window through ``obs.step_profile`` —
    the worker-side wiring of the step-phase profiler: HLO scope
    sets from the model's active executable, FLOPs from its cost
    analysis, peak from the device kind (None off-TPU: the CPU mesh
    still gets the time decomposition, just no absolute MFU)."""
    from theanompi_tpu.obs import format_profile, step_profile
    from theanompi_tpu.utils.scaling_model import (
        cost_analysis_totals,
        peak_flops_per_chip,
    )

    devices = list(model.mesh.devices.flat)
    peak = peak_flops_per_chip(devices)
    nb = model.data.n_batch_train
    k = model.preferred_chunk(nb) if hasattr(
        model, "preferred_chunk") else 1
    prof_rec = Recorder(verbose=False)

    # the window walks SEQUENTIAL in-epoch indices: a streaming feed
    # (loader_pipeline) only overlaps on a sequential stream — pinning
    # index 0 would resync the producer every call and profile a feed
    # that never pipelines (the configured path, measured wrong)
    cursor = {"i": 0}

    def window():
        i = cursor["i"]
        if k > 1:
            model.train_chunk(i, k, prof_rec)
        else:
            model.train_iter(i, prof_rec)
        cursor["i"] = 0 if i + 2 * k > nb else i + k
        prof_rec.flush()

    window()    # stage inputs / warm (executables are already warm)
    hlo = model.train_step_hlo_text()
    flops = bytes_acc = None
    try:
        flops, bytes_acc = cost_analysis_totals(
            model.train_step_cost_analysis(), n_devices
        )
    except Exception:
        pass
    # the streaming feed's staging marker is a SEPARATE executable
    # (data/pipeline.HostStager._mark, scope "host_load"): its HLO
    # rides along as an aux module so the profiler attributes the
    # residual feed cost instead of filing it under host_gap
    aux = []
    if hasattr(model, "stage_hlo_text"):
        stage_hlo = model.stage_hlo_text()
        if stage_hlo:
            aux.append(stage_hlo)
    prof = step_profile(
        window, hlo_text=hlo, n_steps=k, n_devices=n_devices,
        name=type(model).__name__, peak_flops=peak,
        step_flops=flops or None, step_bytes=bytes_acc or None,
        aux_hlo_texts=tuple(aux),
    )
    if verbose:
        print(format_profile(prof), flush=True)
    return {
        "profile": prof.as_dict(),
        "profile_spans": prof.spans(process="bsp_worker"),
        "profile_counters": prof.counter_tracks(process="bsp_worker"),
    }


def run(
    devices: Sequence[Any] | None = None,
    modelfile: str = "",
    modelclass: str = "",
    *,
    config: dict | None = None,
    exch_strategy: str | None = None,
    n_epochs: int | None = None,
    checkpoint_dir: str | None = None,
    resume: bool = False,
    print_freq: int = 40,
    verbose: bool = True,
    **extra: Any,
) -> dict:
    """Train ``modelclass`` under BSP; returns a summary dict."""
    # set-up phases (obs/setup.py): from here to the first fence
    setup = begin_setup()
    meter = setup.meter
    Model = _resolve_model(modelfile, modelclass)
    cfg = dict(config or {})
    cfg.update(extra)
    # resolve the exchange knobs (strategy, buckets, compression)
    # BEFORE the (possibly multi-minute) model build so a typo fails
    # in milliseconds, and so the run summary carries what the model's
    # compile resolves by the same rules
    exchange = ExchangePlan.from_config(cfg, exch_strategy)
    if str(cfg.get("elastic_batch_policy", "global")) \
            not in ELASTIC_BATCH_POLICIES:
        raise ValueError(
            f"elastic_batch_policy must be one of "
            f"{ELASTIC_BATCH_POLICIES}, got "
            f"{cfg.get('elastic_batch_policy')!r}"
        )
    # elastic resume (config['elastic']): a relaunch at a different
    # world width first FITS the world to the batch constraint (an
    # odd surviving device count idles the remainder rather than
    # crash-looping), then rescales batch/LR per elastic_batch_policy
    # BEFORE the pipeline is sized; model.load reshards the flat
    # exchange state onto the new layout instead of refusing
    elastic = bool(cfg.get("elastic"))
    if elastic and resume and checkpoint_dir:
        devices = _elastic_trim_devices(
            devices, cfg, checkpoint_dir, verbose
        )
    mesh = _build_mesh(devices, cfg)
    n_replicas = dp_replicas(mesh)
    n_devices = int(mesh.devices.size)
    # the run's memory account (obs/memory.py): a sample at the end of
    # each set-up phase, at the first fence and at the summary
    memory = begin_memory_account(mesh.devices.flat)
    setup.on_phase_end = memory.sample
    if n_epochs is not None:
        cfg["n_epochs"] = n_epochs
    elastic_note = (
        _apply_elastic_policy(cfg, n_replicas, checkpoint_dir, verbose)
        if elastic and resume and checkpoint_dir else None
    )
    with setup.phase("build_model"):
        model = Model(cfg)
        model.build_model(n_replicas=n_replicas)
    with setup.phase("compile_iter_fns"):
        model.compile_iter_fns(
            mesh=mesh, exch_strategy=exchange.strategy.name
        )

    recorder = Recorder(
        rank=0, size=n_replicas, print_freq=print_freq, verbose=verbose
    )
    # span tracing (theanompi_tpu/obs, config knob "trace"): each
    # sampled iteration becomes one trace — the recorder.phase()
    # spans (load, dispatch, fence, end_epoch, ...) under the root
    # riding the iteration-boundary heartbeat below; dump with
    # config["trace_export"] = path (Perfetto-openable JSON)
    tracer = None
    if cfg.get("trace"):
        from theanompi_tpu.obs import Tracer

        tracer = Tracer(
            process="bsp_worker",
            sample=int(cfg.get("trace_sample", 1)),
        )
        recorder.attach_tracer(tracer)
        recorder.trace_boundary()   # labels default to n_iter —
        # cumulative recorded iterations, correct across resumes
    # graceful preemption: SIGTERM → checkpoint at the next iteration
    # boundary (meta stamps next_iter) and exit 0 — a planned
    # preemption loses zero steps instead of the whole epoch
    with setup.phase("resume"):
        start_iter, resumed_from = _sup.begin_resilient_run(
            model, recorder, checkpoint_dir, resume, verbose=verbose
        )
    resharded = getattr(model, "resharded_from", None)
    if (
        elastic_note and elastic_note.get("lr_scale")
        and resumed_from is not None
    ):
        # load() restored the OLD world's scheduled lr from the
        # checkpoint meta, undoing the pre-build config scaling —
        # re-apply the linear rule to the restored value (which
        # respects the schedule position).  Gated on an ACTUAL
        # restore: when every checkpoint failed validation the
        # cfg-scaled lr already stands, and rescaling again would
        # silently square the factor.
        model.current_lr = float(model.current_lr) * float(
            elastic_note["lr_scale"]
        )
        if verbose:
            print(
                f"elastic resume: lr rescaled to {model.current_lr:g} "
                f"(x{elastic_note['lr_scale']:g})",
                flush=True,
            )

    data = model.data
    if elastic_note and start_iter and elastic_note.get("saved_global"):
        # a mid-epoch next_iter was stamped in the OLD global-batch
        # grid; continue at the same SAMPLE offset in the new grid
        # (floored to a batch boundary — under the 'global' policy the
        # grids coincide and this is the identity)
        old_gb = int(elastic_note["saved_global"])
        new_gb = int(data.global_batch)
        if old_gb != new_gb:
            rescaled = (start_iter * old_gb) // new_gb
            if verbose and rescaled != start_iter:
                print(
                    f"elastic resume: mid-epoch iter {start_iter} "
                    f"(global batch {old_gb}) -> iter {rescaled} "
                    f"(global batch {new_gb})",
                    flush=True,
                )
            start_iter = rescaled
            resumed_from = [model.epoch, start_iter]
    if verbose:
        print(
            f"BSP: {n_replicas} replicas, {data.n_batch_train} train batches"
            f" x {data.global_batch} global batch, "
            f"exchange={exchange.strategy.name}"
            + (" (ZeRO-1 sharded optimizer)" if exchange.zero1 else "")
            + (f", buckets {exchange.bucket_mb:g} MiB"
               if exchange.bucket_mb else
               ", monolithic exchange")
            + (
                f", {exchange.compression} wire"
                + ("+EF" if exchange.error_feedback else " (no EF)")
                if exchange.compression else ""
            ),
            flush=True,
        )

    # a compile after the warm-up, with the iteration it was seen at:
    # the program's own answer to "which step recompiled"
    late_compiles: list[dict] = []
    n_late_compiles = 0
    compiled = meter.read()

    def after_fence_and_compiles(epoch: int) -> None:
        nonlocal compiled, n_late_compiles
        if not setup.closed:
            # the warm-up (and the set-up) ends at the first fence
            if recorder.first_fence_end is not None:
                setup.close(at=recorder.first_fence_end)
                memory.sample(FIRST_FENCE)
                compiled = meter.read()
            return
        if meter.programs == compiled["programs"]:
            return
        new = meter.since(compiled)
        compiled = meter.read()
        n_late_compiles += new["programs"]
        note = {"iteration": recorder.n_iter, "epoch": epoch,
                "programs": new["programs"],
                "compile_s": new["compile_s"],
                "last_program": meter.last_program}
        if len(late_compiles) < 64:
            late_compiles.append(note)
        with recorder.phase("compile", **note):
            pass    # an instant on the profiler's clock and in the ring

    setup.open_phase("warmup")
    preempted = False
    i = 0
    while model.epoch < model.n_epochs:
        epoch = model.epoch
        recorder.start_epoch()
        if hasattr(data, "shuffle"):
            with recorder.phase("shuffle", epoch=epoch):
                data.shuffle(epoch)  # same epoch → same permutation,
                # so a mid-epoch resume continues the identical batch
                # sequence
        nb = data.n_batch_train
        i = start_iter
        start_iter = 0
        while i < nb:
            # device-resident models batch K steps per dispatch
            # (steps_per_call config knob); everything else is the
            # classic one-step loop
            k = model.preferred_chunk(nb - i) if hasattr(
                model, "preferred_chunk") else 1
            if k > 1:
                model.train_chunk(i, k, recorder)
            else:
                model.train_iter(i, recorder)
            i += k
            recorder.print_train_info(i - 1)
            _faults.maybe_inject_fault(epoch, i - k, i - 1,
                                       checkpoint_dir=checkpoint_dir,
                                       world=n_devices)
            after_fence_and_compiles(epoch)
            recorder.trace_boundary()
            _sup.heartbeat(recorder.n_iter, epoch, i - 1,
                           resumed_from=resumed_from,
                           world_size=n_replicas,
                           resharded=bool(resharded))
            if _sup.preemption_requested():
                preempted = True
                break
        if preempted:
            break

        if data.n_batch_val:
            with recorder.phase("validate", epoch=epoch):
                tot_l = tot_e = tot_e5 = 0.0
                for j in range(data.n_batch_val):
                    l, e, e5 = model.val_iter(j, recorder)
                    tot_l += l
                    tot_e += e
                    tot_e5 += e5
                nv = data.n_batch_val
                recorder.val_error(tot_l / nv, tot_e / nv, tot_e5 / nv)

        recorder.end_epoch(epoch)
        after_fence_and_compiles(epoch)
        if os.environ.get("TM_DEBUG_SYNC") == "1":
            # SURVEY §5.2 debug mode: the chips must hold identical
            # replicated params after a full epoch of exchanges
            from theanompi_tpu.parallel.debug import check_replicas_synced

            spread = check_replicas_synced(model.params, strict=True)
            if verbose:
                print(f"debug-sync epoch {epoch}: spread={spread:g}",
                      flush=True)
        with recorder.phase("adjust_hyperp", epoch=epoch + 1):
            model.adjust_hyperp(epoch + 1)
        if checkpoint_dir:
            with recorder.phase("checkpoint", epoch=epoch):
                model.save(checkpoint_dir, recorder)
        model.epoch += 1

    if preempted:
        if checkpoint_dir:
            recorder.fence()  # in-flight steps end before the save
            with recorder.phase("checkpoint", epoch=model.epoch):
                model.save(checkpoint_dir, recorder,
                           extra_meta={"next_iter": i, "preempted": True})
        if verbose:
            print(
                f"preempted: checkpointed epoch {model.epoch} iter {i}, "
                f"exiting cleanly", flush=True,
            )
        _sup.heartbeat(recorder.n_iter, model.epoch, i,
                       status="preempted", world_size=n_replicas,
                       resharded=bool(resharded))
    else:
        _sup.heartbeat(recorder.n_iter, model.epoch, None,
                       status="completed", world_size=n_replicas,
                       resharded=bool(resharded))
    # give an in-process host its normal SIGTERM semantics back
    _sup.uninstall_preemption_handler()
    setup.close()   # a run that never reached a fence ends it here
    memory.sample(SUMMARY)      # what a step starts from
    memory.rule = model.keep_account(model.keep_bytes_limit)
    if verbose:
        print(memory.format(), flush=True)

    # step-phase profiler (config knob "step_profile", ISSUE 15): one
    # profiled window AFTER training — per-scope decomposition with
    # MFU/gap attribution attached to the summary.  Runs extra steps
    # on the final params (a post-run diagnostic, never on by
    # default) against a throwaway recorder so the run's telemetry
    # stays untouched.  A profiler failure is reported, not fatal —
    # it must not cost a completed multi-hour run its summary.
    step_prof = None
    if cfg.get("step_profile") and not preempted:
        try:
            step_prof = _profile_step_phase(model, n_devices, verbose)
        except Exception as e:  # pragma: no cover - diagnostic path
            step_prof = {"error": f"{type(e).__name__}: {e}"}
            if verbose:
                print(f"step_profile failed: {e}", flush=True)

    trace_spans = None
    if tracer is not None:
        recorder.finish_trace()
        trace_spans = tracer.stats()["n_spans"]
        if cfg.get("trace_export"):
            from theanompi_tpu.obs import write_chrome_trace

            # the StepProfile rides the SAME export as the iteration
            # spans — phase tree + counter tracks in one Perfetto view
            spans = tracer.spans()
            counters = None
            if isinstance(step_prof, dict) and "profile" in step_prof:
                spans = spans + step_prof["profile_spans"]
                counters = step_prof["profile_counters"]
            write_chrome_trace(spans, cfg["trace_export"],
                               counters=counters)
            if verbose:
                print(f"trace: {trace_spans} spans -> "
                      f"{cfg['trace_export']}", flush=True)
    if isinstance(step_prof, dict):
        # the span/counter payloads only ride the export file
        step_prof = step_prof.get("profile", step_prof)

    # capture the stream cursor (staged/starved delivery counters)
    # BEFORE parking the producer — the stall_loader drill asserts the
    # degrade path ticked, and close_feed drops the loader
    loader_stats = None
    feed = getattr(model, "_feed", None)
    if feed is not None:
        loader_stats = feed.cursor()
    if hasattr(model, "close_feed"):
        model.close_feed()  # park the streaming feed's producer thread

    last_val = recorder.val_records[-1] if recorder.val_records else {}
    rule = memory.rule or {}
    kept = rule.get("kept", {})
    return {
        "epochs": model.epoch,
        "exch_strategy": exchange.strategy.name,
        # checkpoint names the model's per-layer remat keeps ([] when
        # it keeps everything, or the model has no such remat)
        "remat_saves": list(getattr(model, "remat_saves", ())),
        # of its "remat_calls" layer calls a step, the last
        # "remat_kept_calls" dense ones also keep the MLP's gate and
        # up products, the last "remat_kept_attn_calls" grouped-query
        # attention ones q, k, v and the attention block's output and
        # the last "remat_kept_moe_calls" dropless expert ones their
        # sorted rows with the gate and up products,
        # "remat_kept_bytes" on a device in all: the memory account's
        # rule, under the names these fields had before it
        "remat_calls": getattr(model, "remat_calls", 0),
        "remat_kept_calls": kept.get("mlp", 0),
        "remat_kept_attn_calls": kept.get("attn", 0),
        "remat_kept_moe_calls": kept.get("moe", 0),
        "remat_kept_bytes": rule.get("kept_bytes", 0),
        # the flash kernels' tiles for the model's attention shape
        # ({} where no such kernel runs)
        # (a model whose attention is described layer by layer: a
        # summary an attention kind; "attention_kinds" counts the
        # layers of each, "sliding_window" is the window layers' reach)
        "flash_tiles": getattr(model, "flash_tiles", dict)(),
        "attention_kinds": getattr(model, "attention_kinds", None),
        "sliding_window": getattr(model, "sliding_window", None),
        # the attention path, the experts an expert layer's leaves
        # hold (None: all it routes over) and the depth of the
        # multi-token-prediction module
        "attention": getattr(model, "attention", None),
        "experts_held": getattr(model, "moe_experts_held", None),
        "mtp_depth": getattr(model, "mtp_depth", 0),
        # layers of each mixer kind ("attention", "mamba") and the
        # state-space scan's chunk (None without a mamba layer)
        "mixer_kinds": getattr(model, "mixer_kinds_count", None),
        # blocks of each kind of a stack described block by block
        # (``layer_types`` a string: "M" / "*" / "E"; None for whole layers),
        # and the heads and groups a head share HOLDS beside the
        # published counts, ``{count: [held, published]}`` ({}: all)
        "block_kinds": getattr(model, "block_kinds_count", None),
        "head_share": {k: list(v) for k, v in
                       getattr(model, "head_share", {}).items()},
        "ssd_chunk": getattr(model, "ssd_chunk", None),
        # query heads a layer, whether a sigmoid gate a head multiplies
        # the attention kernels' output, and how many of a head's
        # channels each attention kind rotates
        "heads_per_layer": getattr(model, "heads_per_layer", None),
        "attention_gate": getattr(model, "attention_gate", False),
        "rotary_channels": getattr(model, "rotary_channels", None),
        # the tiles the scan's kernels took ({} where XLA's form runs)
        "ssd_kernel": getattr(model, "ssd_kernel", dict)(),
        "exchange_bucket_mb": exchange.bucket_mb,
        "exchange_replicas": getattr(model, "exchange_replicas", None),
        "exchange_buckets": getattr(model, "exchange_buckets", None),
        "exch_compression": exchange.compression or "none",
        "error_feedback": exchange.error_feedback,
        "iterations": recorder.n_iter,
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
        "world_size": n_replicas,
        "n_devices": n_devices,
        "elastic": elastic,
        "elastic_batch_policy": (
            str(cfg.get("elastic_batch_policy", "global"))
            if elastic else None
        ),
        "elastic_resume": elastic_note,
        "resharded": bool(resharded),
        "trace_spans": trace_spans,
        "setup_phases": setup.as_dict(),
        # the keep rule's terms and what it kept and left, the
        # runtime's bytes sample by sample (obs/memory.py)
        "memory": memory.as_dict(),
        # the seconds before this function's entry (obs/setup.py)
        "process_phases": setup.process_phases(),
        "compiles_after_warmup": late_compiles,
        "n_compiles_after_warmup": n_late_compiles,
        # routing counters of the last fenced MoE step (None if dense)
        "moe_counters": recorder.moe_counters,
        # exit counters of a looped decoder's last fenced step
        "ut_counters": recorder.ut_counters,
        # scan counters of a mamba stack's last fenced step
        "ssm_counters": recorder.ssm_counters,
        # gate counters of a gated model's last fenced step
        "attn_gate_counters": recorder.attn_gate_counters,
        "step_profile": step_prof,
        "loader": loader_stats,
        "recorder": recorder,
        "model": model,
    }


if __name__ == "__main__":
    _launcher.worker_main(run)
