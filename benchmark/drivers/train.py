"""Driver of ``kind: train`` traffic: BSP training for a fixed time.

The steps go through ``BSP().init(..., launch="inprocess")`` and the
worker loop of ``workers/bsp_worker.py`` — the rule, the loop, the
recorder and the model's own ``train_chunk`` — never through a direct
call of the jitted step.  The worker loop has no clock of its own to
stop by, and no hook; what it does offer a model file is the
contract's per-epoch ``adjust_hyperp``, which it calls after the
recorder has fenced the epoch's device work.  So the run is laid out
as epochs of ONE scan chunk each (``n_train`` is one chunk's worth of
samples), and the model the rule is given is the configuration's
class with two methods extended in this file:

- ``compile_iter_fns`` keeps a host copy of the initial parameters
  (for the reference check; weights are data);
- ``adjust_hyperp`` stamps the benchmark's clock at each fenced chunk
  boundary, starts and stops the profiler in a traced run, and ends
  training (``n_epochs``) once ``--seconds`` have passed.

Epoch 0 is the warm-up: it compiles (or loads) the scan program and
runs it once.  The window opens at its fence and closes at the fence
of the first chunk that ends after ``--seconds``.

``train_throughput`` is the items of one chunk over the MEAN CHUNK
TIME WITH THE SLOWEST AND THE FASTEST TENTH OF THE CHUNKS LEFT OUT,
per chip (``chunk_rate``).  During twelve minutes of one chip call
(run B, PR 23) single chunks of eight of nine consecutive runs of two
cells took 0.5 to 3 s instead of 0.40 s; no chunk of that call's six
other runs did, nor of the 20 runs of calls C and D: the machine's host,
not the program.  By window totals six of those runs read 2368 to
2544 images/s, a spread no bound the driver admits can hold, while
every run's fastest chunk agreed to 0.1 %.  What the
trimming hides is not dropped: ``stall_share`` (the share of the
window that the slow tenth spent beyond the mean) is a per-layer
metric of every traced run, and the window line prints it with the
rate over the whole window.  In a traced run the chunks the profiler
ran in are left out by their index.

``correct``: every loss of the run is finite; the loss of the FIRST
step equals the plain reference's loss (``reference/<name>.py``,
float32, ``highest`` precision) on the same first batch and the same
initial weights, each replica's shard apart and averaged as the
program's data parallelism does (batch statistics are per replica);
and the run LEARNS: the mean loss of the last chunk is at most the
configuration's ``learns.last_chunk_loss_over_first`` times the first
loss (every epoch is the same chunk of samples, so the loss falls as
they are memorised; a backward pass or an optimizer that lost a part
stops that).  Gradients and logits the program does not give out, so
neither is held to the reference yet (PERF.md, Open questions).
"""

from __future__ import annotations

import importlib
import math
import sys
import time
import types

from .. import flops as flops_lib

#: Relative agreement of the program's first-step loss (bf16 compute,
#: fp32 accumulation and statistics) with the float32 reference.  At
#: initialisation the logits are small and the loss sits near
#: ln(classes), so bf16 rounding reaches it only in the sixth digit:
#: 37 chip runs of PR 23 over the three training cells read 4.5e-7 to
#: 1.3e-5 (PERF.md).  The tolerance is fifteen times the worst of
#: them and fifty times tighter than ``chip_smoke.LOSS_RTOL`` (1e-2,
#: two bf16 runs on different meshes), where it started: a wrong
#: sample order, pooled instead of per-replica batch statistics or a
#: dropped layer move the loss in the second or third digit.
LOSS_RTOL = 2e-4


class _Probe:
    """The benchmark's clock and profiler at the worker loop's fenced
    chunk boundaries."""

    def __init__(self, ctx: dict, trace_chunks: int):
        self.ctx = ctx
        self.trace_chunks = trace_chunks
        self.ticks: list[float] = []
        self.t_window: float | None = None
        self.compiles_at_window: int | None = None
        self.initial_params = None
        self._loop_span = None      # host span over the worker loop

    def on_compiled(self, model) -> None:
        import jax

        self.initial_params = jax.device_get(model.params)

    def on_chunk_fenced(self, model) -> None:
        tracing = self.ctx["tracing"]
        n = len(self.ticks)
        # host spans on the trace's clock: the program's loop between
        # two fences, and this hook's own work at the fence
        if not tracing.enabled:
            return self._at_fence(model, tracing, n)
        if self._loop_span is not None:
            self._loop_span.__exit__(None, None, None)
            self._loop_span = None
        with tracing.span("fence_hook"):
            self._at_fence(model, tracing, n)
        if tracing.active:
            self._loop_span = tracing.span("worker_loop_chunk")
            self._loop_span.__enter__()

    def _at_fence(self, model, tracing, n: int) -> None:
        if n == 0:
            tracing.start()
            self.compiles_at_window = self.ctx["meter"].programs
        elif n == self.trace_chunks:
            tracing.stop()
        now = time.monotonic()
        self.ticks.append(now)
        if n == 0:
            self.t_window = now
        elapsed = now - self.t_window - tracing.overhead_s
        # a traced run times at least one chunk the profiler was not in
        least = self.trace_chunks + 1 if tracing.enabled else 1
        if n >= least and elapsed >= self.ctx["seconds"]:
            model.n_epochs = model.epoch + 1     # the loop ends here

    @property
    def window_s(self) -> float:
        return (self.ticks[-1] - self.t_window
                - self.ctx["tracing"].overhead_s)


def chunk_rate(chunk_s: list[float]) -> dict:
    """The mean chunk time with the slowest and the fastest tenth of
    the chunks left out, and what that leaves out: ``stall_share`` is
    the share of all the chunks' time that the slow tenth spent beyond
    the mean."""
    ordered = sorted(chunk_s)
    trim = len(ordered) // 10
    kept = ordered[trim:len(ordered) - trim]
    mean = sum(kept) / len(kept)
    slow = ordered[len(ordered) - trim:]
    return {
        "chunk_mean_s": mean, "chunks_kept": len(kept),
        "chunk_s_min": ordered[0], "chunk_s_max": ordered[-1],
        "stall_share": sum(t - mean for t in slow) / sum(ordered),
    }


def learns(losses: list[float], k: int, spec: dict | None) -> dict:
    """Whether the run's last chunk sits as far under its first loss
    as the configuration says a healthy run's does (``spec`` is its
    ``learns`` group; without one nothing is asked)."""
    last = sum(losses[-k:]) / k
    out = {"last_chunk_loss": last,
           "last_chunk_loss_over_first": last / losses[0]}
    if spec is None:
        return dict(out, ok=True)
    limit = float(spec["last_chunk_loss_over_first"])
    return dict(out, limit=limit,
                ok=out["last_chunk_loss_over_first"] <= limit)


def _timed_model(base, probe: _Probe):
    """``base`` with the two contract methods extended (see module
    docstring), importable by the rule under a module name."""

    class Timed(base):
        def compile_iter_fns(self, *args, **kw):
            super().compile_iter_fns(*args, **kw)
            probe.on_compiled(self)

        def adjust_hyperp(self, epoch):
            super().adjust_hyperp(epoch)
            probe.on_chunk_fenced(self)

    Timed.__name__ = base.__name__
    module = types.ModuleType("benchmark_timed_model")
    module.Model = Timed
    sys.modules[module.__name__] = module
    return module.__name__, "Model"


def program_config(config: dict, *, seed: int, n_replicas: int) -> dict:
    """The dict the model class is built from: the file's ``program``
    group, the architecture keys it names out of the published ones,
    the seed, and a train set of exactly one scan chunk."""
    from ..run import program_knobs

    cfg = program_knobs(config)
    k = int(cfg["steps_per_call"])
    cfg.update(
        seed=seed,
        device_data_cache=True,
        n_train=k * int(cfg["batch_size"]) * n_replicas,
        # less than one global batch: the data objects round it down
        # to no validation batch at all, and read 0 as "default"
        n_val=1,
        n_epochs=10 ** 9,       # ended by the clock, see _Probe
    )
    return cfg


def _reference_loss(config: dict, params, model, n_replicas: int) -> float:
    """The plain reference's loss on the run's first global batch."""
    import jax
    import numpy as np

    ref_spec = config["reference"]
    ref = importlib.import_module(
        f"{__package__.rsplit('.', 1)[0]}.reference.{ref_spec['module']}"
    )
    model.data.shuffle(0)       # the epoch-0 order the first step saw
    x, y = model.data.train_batch(0)
    loss_fn = jax.jit(
        lambda p, xs, ys: ref.loss(p, xs, ys, **ref_spec.get("kwargs", {}))
    )
    shards = zip(np.split(np.asarray(x), n_replicas),
                 np.split(np.asarray(y), n_replicas))
    return float(np.mean([float(loss_fn(params, xs, ys)) for xs, ys in shards]))


def run(ctx: dict) -> dict:
    import gc

    import jax

    from ..run import memory_peak_bytes

    cell, config = ctx["cell"], ctx["cell"]["config"]
    traffic = cell["traffic"]
    n_dev = len(ctx["devices"])
    cfg = program_config(config, seed=ctx["seed"], n_replicas=n_dev)
    k = int(cfg["steps_per_call"])
    probe = _Probe(ctx, int(traffic["trace_chunks"]))
    base = getattr(importlib.import_module(config["model"]["modelfile"]),
                   config["model"]["modelclass"])
    modelfile, modelclass = _timed_model(base, probe)

    from theanompi_tpu import BSP

    rule = BSP()
    rule.init(devices=list(range(n_dev)), modelfile=modelfile,
              modelclass=modelclass, launch="inprocess", config=cfg,
              verbose=False)
    res = rule.wait()
    compiles_in_window = ctx["meter"].programs - probe.compiles_at_window
    model = res["model"]
    losses = [float(v) for v in res["recorder"].train_losses]
    peak = memory_peak_bytes(ctx["devices"])

    chunks = len(probe.ticks) - 1
    steps = chunks * k
    global_batch = int(model.data.global_batch)
    per_sample = int(cfg["seq_len"]) if config["item"] == "token" else 1
    items_per_step = global_batch * per_sample
    window_s = probe.window_s
    chunk_s = [b - a for a, b in zip(probe.ticks, probe.ticks[1:])]
    if ctx["tracing"].enabled:      # the chunks the profiler ran in
        chunk_s = chunk_s[probe.trace_chunks:]
    rate = chunk_rate(chunk_s)
    throughput = k * items_per_step / rate["chunk_mean_s"] / n_dev
    setup_s = probe.t_window - ctx["t_process"]
    window_losses = losses[k:]
    failed = sum(not math.isfinite(v) for v in window_losses)
    ctx["log"](
        event="window", steps=steps, chunks=chunks, scan_k=k,
        window_s=window_s, global_batch=global_batch, item=config["item"],
        items_per_step=items_per_step, setup_s=setup_s, **rate,
        rate_over_whole_window=steps * items_per_step / window_s / n_dev,
        first_loss=losses[0], last_loss=losses[-1],
        compiles_in_window=compiles_in_window, **ctx["meter"].read(),
        # in order, for whoever asks WHERE in the window a stall fell
        chunk_s=[round(t, 5) for t in chunk_s],
    )

    facts = None
    if ctx["tracing"].enabled:
        spec = config["flops_per_item"]
        hlo_text = model.train_step_hlo_text()
        # too long for the end of the output: kept for who reads next
        (ctx["scratch"] / "step_hlo.txt").write_text(hlo_text)
        facts = {
            "trace": ctx["tracing"].load(),
            "hlo_text": hlo_text,
            "scan_k": k,
            "items_per_step": items_per_step,
            "items_per_s_per_chip": throughput,
            "stall_share": rate["stall_share"],
            "flops_per_item": getattr(flops_lib, spec["fn"])(
                **spec.get("kwargs", {})
            ),
            "memory_peak_bytes": peak,
        }

    # the reference needs the room the trained state holds
    n_replicas = int(res["world_size"])
    model.params = model.opt_state = None
    for attr in ("net_state", "ef_state", "_device_cache", "_seqs_dev"):
        if hasattr(model, attr):
            setattr(model, attr, None)
    del res
    gc.collect()
    jax.clear_caches()
    ref_loss = _reference_loss(config, probe.initial_params, model,
                               n_replicas)
    rel = abs(losses[0] - ref_loss) / abs(ref_loss)
    learnt = learns(losses, k, config.get("learns"))
    correct = (
        failed == 0 and all(math.isfinite(v) for v in losses)
        and rel <= LOSS_RTOL and learnt["ok"] and compiles_in_window == 0
    )
    ctx["log"](event="reference", first_loss=losses[0],
               reference_loss=ref_loss, rel_diff=rel, rtol=LOSS_RTOL,
               learns=learnt, correct=correct)
    return {
        "correct": correct, "attempted": steps, "failed": failed,
        "end_to_end": {"train_throughput": throughput, "setup_s": setup_s},
        "memory_peak_bytes": peak, "facts": facts,
    }
