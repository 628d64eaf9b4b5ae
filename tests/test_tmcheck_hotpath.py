"""tmcheck hot-path sanitizer (theanompi_tpu/analysis/hotpath.py):
TM104 host-sync fences, TM105 value-dependent shapes, TM106
trace-time wall-clock/RNG.  The headline regression fixture is the
PR 6 per-chunk ``int()`` fence in chunked prefill (the bug the
"no per-step value fences" rule retired) —
re-introducing it must be caught, while the post-fix shape (ONE
fence after the loop) stays clean.
"""

import textwrap

from theanompi_tpu.analysis import core, hotpath


def run(src: str) -> list:
    sf = core.SourceFile(textwrap.dedent(src), "fixture.py")
    return core.collect([sf], rule_fns=(hotpath.check_file,))


def rules_of(findings) -> list:
    return [f.rule for f in findings]


class TestHostFences:
    def test_pr6_per_chunk_int_fence_flagged(self):
        # the PR 6 regression: chunked prefill reading each chunk's
        # token back to host inside the chunk loop
        out = run("""
            class Dec:
                def prefill(self, ids, key):
                    pos = 0
                    tok = None
                    while pos < len(ids):
                        out = self._prefill_jit(True)(ids[pos:pos + 8], key)
                        tok = int(out)
                        pos += 8
                    return tok
        """)
        assert rules_of(out) == ["TM104"]
        assert "per-iteration int() fence" in out[0].message

    def test_one_fence_after_loop_clean(self):
        # the post-fix discipline: dispatch stays async, ONE sync at
        # the end (decoder.prefill's documented TTFT fence)
        out = run("""
            class Dec:
                def prefill(self, ids, key):
                    pos = 0
                    out = None
                    while pos < len(ids):
                        out = self._prefill_jit(True)(ids[pos:pos + 8], key)
                        pos += 8
                    return int(out)
        """)
        assert out == []

    def test_untainted_int_in_loop_clean(self):
        # host bookkeeping ints are not fences
        out = run("""
            class Eng:
                def step(self, slots):
                    n = 0
                    for s in slots:
                        n += int(s.budget)
                    return n
        """)
        assert out == []

    def test_item_and_block_until_ready_flagged_anywhere(self):
        out = run("""
            import jax
            import jax.numpy as jnp

            class Dec:
                def decode(self, x):
                    y = jnp.exp(x)
                    jax.block_until_ready(y)
                    return y.item()
        """)
        assert rules_of(out) == ["TM104", "TM104"]

    def test_np_asarray_of_device_value_in_loop_flagged(self):
        out = run("""
            import numpy as np

            class Dec:
                def decode_step(self, chunks):
                    outs = []
                    for c in chunks:
                        y = self._decode_jit(True)(c)
                        outs.append(np.asarray(y))
                    return outs
        """)
        assert rules_of(out) == ["TM104"]

    def test_non_hot_function_exempt(self):
        out = run("""
            class Dec:
                def gather(self, chunks):
                    outs = []
                    for c in chunks:
                        y = self._gather_jit(True)(c)
                        outs.append(int(y))
                    return outs
        """)
        assert out == []

    def test_hot_marker_opts_in(self):
        out = run("""
            class Dec:
                def gather(self, chunks):  # tmcheck: hot
                    outs = []
                    for c in chunks:
                        y = self._gather_jit(True)(c)
                        outs.append(int(y))
                    return outs
        """)
        assert rules_of(out) == ["TM104"]

    def test_test_functions_exempt(self):
        out = run("""
            def test_decode_roundtrip(dec, chunks):
                for c in chunks:
                    assert int(dec_jit(c)) >= 0
        """)
        assert out == []


class TestShapes:
    def test_fence_derived_shape_flagged(self):
        out = run("""
            import jax.numpy as jnp

            class Dec:
                def decode_step(self, lengths):
                    n = int(jnp.max(lengths))
                    return jnp.zeros((n, 4))
        """)
        assert rules_of(out) == ["TM105"]
        assert "one-compile" in out[0].message

    def test_bucketed_shape_clean(self):
        out = run("""
            import jax.numpy as jnp

            class Dec:
                def decode_step(self, prompt):
                    n = self.bucket_for(len(prompt))
                    return jnp.zeros((n, 4))
        """)
        assert out == []


class TestTracedBodies:
    def test_wall_clock_in_jitted_body_flagged(self):
        out = run("""
            import time
            import jax

            class Dec:
                def _decode_body(self, params, x):
                    t = time.time()
                    return x * t

                def build(self):
                    return jax.jit(self._decode_body)
        """)
        assert rules_of(out) == ["TM106"]
        assert "TRACE time" in out[0].message

    def test_host_rng_in_scan_body_flagged(self):
        out = run("""
            import numpy as np
            from jax import lax

            def build(xs):
                def step(carry, x):
                    noise = np.random.randn()
                    return carry + x + noise, x
                return lax.scan(step, 0.0, xs)
        """)
        assert rules_of(out) == ["TM106"]
        assert "jax.random" in out[0].message

    def test_item_in_traced_body_flagged(self):
        out = run("""
            import jax

            @jax.jit
            def decode_step(x):
                return x.item()
        """)
        assert rules_of(out) == ["TM104"]
        assert "tracer" in out[0].message

    def test_wall_clock_in_host_loop_clean(self):
        # engine.step stamps wall time between dispatches — host
        # code, perfectly legal
        out = run("""
            import time

            class Eng:
                def step(self):
                    t0 = time.monotonic()
                    self._decode_once()
                    return time.monotonic() - t0
        """)
        assert out == []

    def test_nested_def_inside_traced_body_is_traced(self):
        out = run("""
            import time
            import jax

            def build():
                def outer(x):
                    def inner(y):
                        return y * time.time()
                    return inner(x)
                return jax.jit(outer)
        """)
        assert rules_of(out) == ["TM106"]


class TestSuppressionTracking:
    def test_suppressed_fence_and_stale_marker(self):
        out = run("""
            class Dec:
                def prefill(self, ids):
                    toks = []
                    for c in ids:
                        y = self._prefill_jit(True)(c)
                        toks.append(int(y))  # tmcheck: disable=TM104
                    n = len(toks)  # tmcheck: disable=TM104
                    return toks
        """)
        # the loop fence is suppressed; the second marker sits on a
        # clean line and is itself flagged as stale
        assert rules_of(out) == ["TM201"]
        assert "matches no finding" in out[0].message


class TestSpeculativeVerifyFences:
    """The speculative hot path (TM104 seeds "verify"/"draft",
    serving v5): a per-draft-token host fence inside the verify loop
    is the PR 6 per-chunk-fence bug class one level deeper — each
    draft's readback would serialize the verify window the
    fixed-shape executable exists to batch."""

    def test_per_draft_token_int_fence_flagged(self):
        out = run("""
            class Eng:
                def _spec_verify(self, drafts, key):
                    toks = []
                    for d in drafts:
                        out = self._verify_jit(True)(d, key)
                        toks.append(int(out))
                    return toks
        """)
        assert rules_of(out) == ["TM104"]
        assert "per-iteration int() fence" in out[0].message

    def test_one_verify_dispatch_per_window_clean(self):
        # the shipped shape (Engine._spec_decode_once): ONE verify
        # dispatch for the whole window, one readback after
        out = run("""
            import numpy as np

            class Eng:
                def _spec_verify(self, drafts, key):
                    out = self._verify_jit(True)(drafts, key)
                    return np.asarray(out)
        """)
        assert out == []

    def test_drafter_is_hot_but_host_pure_clean(self):
        # the n-gram drafter is seeded ("draft") but touches no
        # device values — pure host list work stays clean
        out = run("""
            class Drafter:
                def draft(self, history, k):
                    out = []
                    for n in range(3, 0, -1):
                        if history[-n:] == history[:n]:
                            out = history[n:n + k]
                            break
                    return out
        """)
        assert out == []


# -- tracer API in hot loops (PR 14: obs/tracer.py seeds) --------------------


class TestTracerSpans:
    """`Tracer.span`/`start_span`/`end_span`/`record_span` are
    hot-name seeds: span bodies must stay host-pure, and a device
    value fenced into a span attribute at a hot call site is the
    per-iteration round trip TM104 exists for."""

    def test_fence_inside_span_attr_in_hot_loop_flagged(self):
        # the known-bad twin: per-slot decode loop reads a device
        # value back just to decorate a span
        out = run("""
            class Eng:
                def _spec_decode_once(self):
                    for slot in range(8):
                        out = jnp.argmax(self.logits[slot])
                        with self.tracer.span(
                            self.ctx, "spec_window", tokens=int(out)
                        ):
                            self.commit(slot)
        """)
        assert "TM104" in rules_of(out)
        assert any("int() fence" in f.message for f in out)

    def test_host_stamp_only_span_clean(self):
        # the clean twin: same loop, same span, attrs are host ints
        out = run("""
            class Eng:
                def _spec_decode_once(self):
                    for slot in range(8):
                        with self.tracer.span(
                            self.ctx, "spec_window",
                            tokens=self._step_tokens,
                        ):
                            self.commit(slot)
        """)
        assert out == []

    def test_span_entry_exit_body_is_hot(self):
        # the API bodies themselves are seeded hot: a tracer
        # implementation that fences a device value on span entry/
        # exit is flagged without any caller involved
        out = run("""
            class Tracer:
                def span(self, ctx, name, value):
                    t0 = self.clock()
                    snapshot = value.item()
                    return (t0, snapshot)
        """)
        assert rules_of(out) == ["TM104"]
        assert ".item()" in out[0].message

    def test_host_pure_span_body_clean(self):
        # the real tracer's shape: monotonic stamps + dict ops only
        out = run("""
            class Tracer:
                def start_span(self, ctx, name, **attrs):
                    if ctx is None:
                        return None
                    return {"name": name, "t0": self.clock(),
                            "attrs": dict(attrs)}

                def end_span(self, handle, **attrs):
                    if handle is None:
                        return None
                    handle["attrs"].update(attrs)
                    handle["t1"] = self.clock()
                    return handle
        """)
        assert out == []


class TestRecorderPhase:
    """`Recorder.phase` (utils/recorder.py), the training path's span
    call, is seeded like the tracer API: attributes are host values."""

    def test_fence_inside_phase_attr_in_hot_loop_flagged(self):
        # the known-bad twin: a loss read back to decorate the span
        out = run("""
            class Model:
                def train_chunk(self, count, k, recorder):  # tmcheck: hot
                    for j in range(k):
                        loss = jnp.mean(self.losses[j])
                        with recorder.phase(
                            "dispatch", first=count, loss=float(loss)
                        ):
                            self.dispatch(j)
        """)
        assert "TM104" in rules_of(out)

    def test_host_attrs_only_phase_clean(self):
        out = run("""
            class Model:
                def train_chunk(self, count, k, recorder):  # tmcheck: hot
                    for j in range(k):
                        with recorder.phase("dispatch", first=count, k=k):
                            self.dispatch(j)
        """)
        assert out == []

    def test_phase_body_is_hot(self):
        # a span call that fences a device value on entry is flagged
        # without any caller involved
        out = run("""
            class Recorder:
                def phase(self, name, value):
                    self.last = value.item()
                    return self
        """)
        assert rules_of(out) == ["TM104"]


class TestLoaderProducerFences:
    """The streaming loader's producer loop (TM104 seeds "next"/
    "_produce", ISSUE 16): the whole point of the producer thread is
    fetch+stage UNDER the previous step's compute, so a per-batch
    value readback inside it re-serializes exactly what the pipeline
    overlapped — the PR 6 fence bug class relocated to the feed."""

    def test_per_batch_float_fence_in_producer_flagged(self):
        out = run("""
            class Loader:
                def _produce(self):
                    while True:
                        batch = self._fetch(self._next_prod)
                        staged = self._stage_jit(batch)
                        self._checksum += float(staged[0])
                        self._ring.append(staged)
        """)
        assert rules_of(out) == ["TM104"]
        assert "per-iteration float() fence" in out[0].message

    def test_stage_without_value_read_clean(self):
        # the shipped shape: stage and enqueue — the ring bounds
        # in-flight transfers by COUNT, never by a host fence
        out = run("""
            class Loader:
                def _produce(self):
                    while True:
                        batch = self._fetch(self._next_prod)
                        staged = self._stage_jit(batch)
                        self._ring.append(staged)
        """)
        assert out == []

    def test_consumer_next_is_seeded_hot(self):
        # "next" (HOT_EXACT): a consumer that blocks on the staged
        # value itself — rather than popping the ring — is flagged
        out = run("""
            class Loader:
                def next(self, i):
                    staged = self._stage_jit(self._fetch(i))
                    staged[0].block_until_ready()
                    return staged
        """)
        assert rules_of(out) == ["TM104"]
