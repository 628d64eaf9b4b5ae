"""Driver of ``kind: open_loop`` traffic: a served model under load
that arrives on a schedule.

The system under test is built the way a replica builds it, from the
program's public pieces: ``Llama(config)`` with weights from the seed
(no training), ``model.make_decoder(paged=True, ...)`` and an
``Engine`` on its own thread (``Engine.start()``).  The generator
(``loadgen.OpenLoop``) runs in this process on one more thread and
submits each request when it is due.

Set-up warms the two programs this traffic uses — one prefill chunk
shape and the greedy decode step — with two short requests.  Then the
window: arrivals for ``--seconds``, and at most ``drain_s`` more for
what is in flight.  A request counts when it was DUE inside the window
and came back ``ok`` with all its tokens before the drain limit; shed,
failed or unfinished requests are missing, and a missing request is
beyond every percentile.

``correct``: for a seeded sample of completed requests, every served
token is within ``LOGIT_RTOL`` of the plain reference's top logit at
its position (``reference/decoder.py``: full float32 forward of the
prompt and the stream's own earlier tokens — prefill and decode
through the paged cache must agree with it), and the sample holds more
distinct tokens than requests (a model that says one thing passes any
check of what it says).  Logits and not tokens are compared: with
random weights the top two logits are often closer than bf16 resolves.
"""

from __future__ import annotations

import math
import time

import numpy as np

from .. import loadgen

#: a served token's reference logit may lie below the row's top logit
#: by this share of it: 4 bf16 eps, the tolerance ``chip_smoke`` states
#: for the same comparison (bf16 decides near-ties differently on
#: different code paths; a wrong mask, position or block is off by the
#: whole logit range, and float16 or int8 compute by far more than 3%)
LOGIT_RTOL = 4 * 2.0 ** -7
#: requests of the reference sample are padded to this many positions,
#: so one reference program serves them all
REFERENCE_POSITIONS = 2048
REFERENCE_SAMPLE = 8


def percentile(values: list[float], q: float) -> float:
    """The ``q``-th percentile by nearest rank (no interpolation: a
    tail is a reading that happened)."""
    ordered = sorted(values)
    return ordered[min(len(ordered) - 1, math.ceil(q / 100 * len(ordered)) - 1)]


def program_config(config: dict, *, seed: int) -> dict:
    from ..run import program_knobs

    cfg = program_knobs(config)
    # a replica holds weights only: no data set beyond one batch
    cfg.update(seed=seed, tp=1, device_data_cache=False,
               n_train=int(cfg["batch_size"]), n_val=0)
    return cfg


def build(ctx: dict):
    """``(model, decoder, engine, recorder)``, the engine started and
    both programs warm."""
    from theanompi_tpu.models.llama import Llama
    from theanompi_tpu.parallel import dp_replicas, make_mesh
    from theanompi_tpu.serving import Engine
    from theanompi_tpu.utils.recorder import ServingRecorder

    config = ctx["cell"]["config"]
    serving = config["serving"]
    with ctx["tracing"].span("build_model"):
        model = Llama(program_config(config, seed=ctx["seed"]))
        mesh = make_mesh(data=1, model=1, devices=ctx["devices"][:1])
        model.build_model(n_replicas=dp_replicas(mesh))
        model.compile_iter_fns(mesh=mesh)
        decoder = model.make_decoder(paged=True, **serving["decoder"])
    recorder = ServingRecorder(max_slots=decoder.max_slots,
                               max_samples=1 << 20)
    # the engine's own spans (obs/tracer.py) only in the traced run:
    # the queue wait of a served request is nowhere else
    engine = Engine(decoder, recorder=recorder,
                    trace_sample=1 if ctx["tracing"].enabled else 0,
                    **serving["engine"])
    engine.start()
    rng = np.random.default_rng([ctx["seed"], 0xA11])
    chunk = decoder.prefill_chunk
    warm = [
        engine.submit([int(t) for t in rng.integers(1, model.vocab, n)],
                      max_tokens=4, seed=i)
        for i, n in enumerate((chunk + 3, 2 * chunk + 5))
    ]
    for f in warm:
        r = f.result(timeout=1100)
        if r.status != "ok":
            raise RuntimeError(f"warm-up request came back {r}")
    return model, decoder, engine, recorder


def window(ctx: dict, engine, traffic: dict, *, seed: int, seconds: float,
           vocab: int, trace_seconds: float = 0.0) -> dict:
    """Offer ``traffic`` for ``seconds`` and return what came back."""
    planned = loadgen.plan(traffic, seed=seed, seconds=seconds, vocab=vocab)
    tracing = ctx["tracing"]

    def submit(p: loadgen.Planned):
        with tracing.span("submit"):
            return engine.submit(p.prompt, max_tokens=p.max_tokens,
                                 temperature=0.0)

    gen = loadgen.OpenLoop(planned, submit)
    if trace_seconds:
        tracing.start()
    compiles0 = ctx["meter"].programs
    t0_wall = time.time()
    t0 = gen.start()
    if trace_seconds:
        time.sleep(trace_seconds)
        tracing.stop()
    limit = t0 + seconds + float(traffic["drain_s"])
    gen.join(max(0.0, t0 + seconds - time.monotonic()) + 5.0)
    done = []
    for p, fut, late in gen.sent:
        try:
            r = fut.result(timeout=max(0.0, limit - time.monotonic()))
        except TimeoutError:
            r = None
        done.append((p, r, late))
    t_end = time.monotonic()
    rows = []
    for p, r, late in done:
        ok = (r is not None and r.status == "ok"
              and len(r.tokens) == p.max_tokens)
        rows.append({
            "planned": p, "ok": ok, "late_s": late, "result": r,
            "ttft_s": (r.ttft_s + late) if ok else math.inf,
            "tpot_s": r.tpot_s if ok else math.inf,
            "queued_s": _queue_wait(r) if ok else None,
        })
    return {
        "rows": rows, "attempted": len(planned),
        "failed": len(planned) - sum(r["ok"] for r in rows),
        "seconds": seconds, "t0_wall": t0_wall, "t0": t0,
        "drained_s": t_end - (t0 + seconds),
        "lateness": gen.lateness(),
        "compiles_in_window": ctx["meter"].programs - compiles0,
    }


def _queue_wait(result) -> float | None:
    """Seconds from submit to admission: the request's
    ``engine_queue`` span (``Result.queued_s`` is filled only for shed
    requests).  None where the engine kept no spans."""
    for span in result.spans:
        if span["name"] == "engine_queue":
            return span["t1"] - span["t0"]
    return None


def end_to_end(win: dict) -> dict:
    rows = win["rows"]
    ok = [r for r in rows if r["ok"]]
    return {
        "serve_tokens_per_s": (
            sum(len(r["result"].tokens) for r in ok) / win["seconds"]
        ),
        "ttft_p90_ms": 1e3 * percentile([r["ttft_s"] for r in rows], 90),
        "tpot_p90_ms": 1e3 * percentile([r["tpot_s"] for r in rows], 90),
    }


def _hold_to_reference(ctx: dict, model, rows: list, seed: int,
                       n_out: int) -> dict:
    """Teacher-forced check of a seeded sample of served streams (the
    arithmetic of ``chip_smoke._hold_to_reference``)."""
    import jax
    import jax.numpy as jnp

    from ..reference import decoder as ref

    t_ref = min(REFERENCE_POSITIONS, int(model.seq_len))
    fits = [r for r in rows if r["ok"]
            and len(r["planned"].prompt) + r["planned"].max_tokens <= t_ref]
    rng = np.random.default_rng([seed, 0x5A])
    picks = rng.choice(len(fits), min(REFERENCE_SAMPLE, len(fits)),
                       replace=False) if fits else []
    logits_fn = jax.jit(lambda p, ids, at: ref.logits_at(
        p, ids, at, n_heads=model.n_heads, n_kv_heads=model.n_kv_heads))
    worst, n_tokens, n_argmax, distinct = 0.0, 0, 0, set()
    for i in picks:
        prompt = fits[i]["planned"].prompt
        tokens = list(fits[i]["result"].tokens)
        seq = np.zeros((t_ref,), np.int32)
        seq[:len(prompt) + len(tokens) - 1] = prompt + tokens[:-1]
        at = np.full((n_out,), len(prompt) - 1, np.int32)
        at[:len(tokens)] = len(prompt) - 1 + np.arange(len(tokens))
        rows_ = np.asarray(
            logits_fn(model.params, jnp.asarray(seq), jnp.asarray(at))
        )[:len(tokens)]
        top = rows_.max(-1)
        gaps = (top - rows_[np.arange(len(tokens)), tokens]) / np.abs(top)
        worst = max(worst, float(gaps.max()))
        n_tokens += len(tokens)
        n_argmax += int((gaps == 0.0).sum())
        distinct.update(tokens)
    held = {
        "sample": len(picks), "tokens": n_tokens, "argmax": n_argmax,
        "worst_gap_over_top": worst, "rtol": LOGIT_RTOL,
        "distinct_tokens": len(distinct),
    }
    held["ok"] = bool(
        len(picks) > 0 and worst <= LOGIT_RTOL and len(distinct) > len(picks)
    )
    ctx["log"](event="reference", **held)
    return held


def run(ctx: dict) -> dict:
    from ..run import memory_peak_bytes

    cell = ctx["cell"]
    traffic = cell["traffic"]
    model, decoder, engine, recorder = build(ctx)
    trace_seconds = (
        min(float(traffic["trace_seconds"]), ctx["seconds"])
        if ctx["tracing"].enabled else 0.0
    )
    try:
        win = window(ctx, engine, traffic, seed=ctx["seed"],
                     seconds=ctx["seconds"], vocab=model.vocab,
                     trace_seconds=trace_seconds)
    finally:
        engine.stop()
    peak = memory_peak_bytes(ctx["devices"])
    setup_s = win["t0"] - ctx["t_process"]
    e2e = end_to_end(win)
    ok_rows = [r for r in win["rows"] if r["ok"]]
    ctx["log"](
        event="window", attempted=win["attempted"], failed=win["failed"],
        n_ok=len(ok_rows), seconds=win["seconds"],
        drained_s=win["drained_s"], generator_lateness=win["lateness"],
        compiles_in_window=win["compiles_in_window"], setup_s=setup_s,
        statuses=sorted({
            "unfinished" if r["result"] is None else
            f"{r['result'].status}:{r['result'].finish_reason}"
            for r in win["rows"] if not r["ok"]
        }),
        ttft_ms_median=1e3 * percentile([r["ttft_s"] for r in win["rows"]], 50),
        tpot_ms_median=1e3 * percentile([r["tpot_s"] for r in win["rows"]], 50),
        **e2e, **ctx["meter"].read(),
    )
    for name, value in e2e.items():
        if not math.isfinite(value):
            raise RuntimeError(
                f"{name} is not finite: more than a tenth of the requests "
                f"are missing ({win['failed']} of {win['attempted']})"
            )
    # one reference program whatever the seed drew: rows for the
    # longest output the traffic can ask for
    held = _hold_to_reference(ctx, model, win["rows"], ctx["seed"],
                              int(traffic["output_tokens"]["max"]))
    facts = None
    if ctx["tracing"].enabled:
        t0, t1 = win["t0_wall"], win["t0_wall"] + win["seconds"]
        facts = {
            "trace": ctx["tracing"].load(),
            "engine_steps": [s for s in recorder.steps if t0 <= s["t"] <= t1],
            "max_slots": decoder.max_slots,
            "queued_s": [r["queued_s"] for r in ok_rows
                         if r["queued_s"] is not None],
            "memory_peak_bytes": peak,
        }
    return {
        "correct": held["ok"] and win["compiles_in_window"] == 0,
        "attempted": win["attempted"], "failed": win["failed"],
        "end_to_end": dict(e2e, setup_s=setup_s),
        "memory_peak_bytes": peak, "facts": facts,
    }
