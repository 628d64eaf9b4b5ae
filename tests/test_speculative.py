"""Speculative decoding (serving v5): self-drafted k-token verify
steps must be INVISIBLE in the outputs — bitwise-identical token
streams and finish reasons vs the sequential non-speculative path, at
every temperature (sampling is deterministic given seed + position),
across tp layouts, and through every k-token bookkeeping edge: EOS
mid-draft-window (exact count, no overshoot), accept-rate 0
(degenerates to one token/step), max_seq hit inside a verify window,
and block scarcity (the window degrades before a request dies).
Telemetry: accept-rate and tokens/step land in ``ServingRecorder``
and survive the fleet merge.
"""

import pytest

from theanompi_tpu.serving import Engine, NGramDrafter
from theanompi_tpu.utils.recorder import FleetRecorder, ServingRecorder
from theanompi_tpu.utils.scaling_model import speculation_speedup

from test_serving_paged import SMALL, build_paged
from test_serving import build_decoder

pytestmark = pytest.mark.serving

# repetitive continuations — the regime self-drafting feeds on
PROMPTS = [
    [5, 9, 5, 9, 5, 9, 5],
    [3, 3, 3, 3, 3],
    [1, 2, 3, 1, 2, 3],
    [7, 11, 7, 11, 7, 2],
    [4, 8, 15, 4, 8, 15],
    [2, 2, 9, 2, 2, 9],
]


def serve(dec, prompts, *, max_tokens=12, temps=None, eos_id=None,
          **ekw):
    eng = Engine(dec, prefix_caching=False, eos_id=eos_id, **ekw)
    futs = [
        eng.submit(p, max_tokens=max_tokens, seed=i,
                   temperature=(temps[i] if temps else 0.0))
        for i, p in enumerate(prompts)
    ]
    eng.run_until_idle()
    rs = [f.result(timeout=0) for f in futs]
    assert all(r.status == "ok" for r in rs)
    return (
        [r.tokens for r in rs],
        [r.finish_reason for r in rs],
        eng,
    )


class TestDrafter:
    def test_prompt_lookahead_finds_repetition(self):
        d = NGramDrafter(max_n=3)
        # trailing 3-gram [9, 5, 9] matches at index 1; the
        # continuation [5, 9] is what's left of the history
        assert d.draft([5, 9, 5, 9, 5, 9], 3) == [5, 9]
        # with more history an earlier match fills the full window
        assert d.draft([5, 9] * 5, 3) == [5, 9, 5]

    def test_longest_ngram_wins(self):
        d = NGramDrafter(max_n=3)
        # trailing 3-gram [1,2,3] matches the front (→ 7), while the
        # 1-gram [3] would match the later 3 (→ 9): longest first
        assert d.draft([1, 2, 3, 7, 3, 9, 1, 2, 3], 1) == [7]

    def test_no_match_returns_empty(self):
        d = NGramDrafter()
        assert d.draft([1, 2, 3, 4], 3) == []
        assert d.draft([], 3) == []
        assert d.draft([1, 2], 0) == []

    def test_scan_window_bounded(self):
        d = NGramDrafter(max_scan=8)
        hist = [9, 9] + [0] * 100 + [1, 2]   # repetition out of window
        assert d.draft(hist, 2) == []

    def test_validation(self):
        with pytest.raises(ValueError):
            NGramDrafter(max_n=1, min_n=2)


class TestBitwiseEquivalence:
    @pytest.mark.parametrize("tp", [1, 2])
    def test_greedy_bitwise_and_reasons(self, devices8, tp):
        dec = build_paged(devices8, tp=tp)
        ref, ref_fr, _ = serve(dec, PROMPTS[:4])
        got, got_fr, eng = serve(dec, PROMPTS[:4], speculate_k=4)
        assert got == ref and got_fr == ref_fr
        s = eng.recorder.summary()
        assert s["accept_rate"] is not None and s["accept_rate"] > 0
        assert s["tokens_per_step"] > 1.0
        assert dec.n_decode_compiles <= 2

    def test_temperature_bitwise(self, devices8):
        """Deterministic position-folded sampling makes accept-by-
        equality exact at EVERY temperature, not just greedy."""
        dec = build_paged(devices8)
        temps = [0.0, 0.9, 0.7, 1.3]
        ref, _, _ = serve(dec, PROMPTS[:4], temps=temps)
        got, _, _ = serve(dec, PROMPTS[:4], temps=temps, speculate_k=4)
        assert got == ref

    def test_batched_equals_single_request(self, devices8):
        """6 speculative requests through 4 slots (evict + refill
        mid-run) == each request served alone speculatively == the
        non-speculative stream."""
        dec = build_paged(devices8)
        plain, _, _ = serve(dec, PROMPTS)
        alone = []
        for i, p in enumerate(PROMPTS):
            eng = Engine(dec, prefix_caching=False, speculate_k=4)
            f = eng.submit(p, max_tokens=12, seed=i)
            eng.run_until_idle()
            alone.append(f.result(timeout=0).tokens)
        batched, _, _ = serve(dec, PROMPTS, speculate_k=4)
        assert alone == plain
        assert batched == plain

    def test_composes_with_pallas_kernel(self, devices8):
        dec_g = build_paged(devices8)
        dec_p = build_paged(
            devices8, paged_attend_impl="pallas", pallas_interpret=True
        )
        ref, _, _ = serve(dec_g, PROMPTS[:4])
        got, _, eng = serve(dec_p, PROMPTS[:4], speculate_k=4)
        assert got == ref
        assert eng.recorder.summary()["accept_rate"] > 0


class _WrongDrafter:
    """Proposes a bitwise-WRONG token for every draft position (the
    true continuation shifted by one in vocab) — deterministic
    accept-rate 0."""

    def __init__(self, truth, prompts, vocab):
        self.truth = {tuple(p): t for p, t in zip(prompts, truth)}
        self.prompts = [list(p) for p in prompts]
        self.vocab = vocab

    def draft(self, history, k):
        for p in self.prompts:
            if history[: len(p)] == p:
                done = len(history) - len(p)
                nxt = self.truth[tuple(p)][done: done + k]
                return [(t + 1) % self.vocab for t in nxt]
        return [0] * k


class TestEdgeCases:
    def test_eos_mid_draft_window_exact_count(self, devices8):
        """Pick the EOS from a known greedy stream so it lands
        INSIDE an accepted window: the speculative run must stop at
        the EOS with the exact same token count — accepted drafts
        past it are discarded, never emitted."""
        dec = build_paged(devices8)
        base, _, _ = serve(dec, PROMPTS[:1], max_tokens=12)
        eos = base[0][len(base[0]) // 2]   # a mid-stream token
        ref, ref_fr, _ = serve(dec, PROMPTS[:1], eos_id=eos)
        got, got_fr, _ = serve(
            dec, PROMPTS[:1], eos_id=eos, speculate_k=4
        )
        assert got == ref and got_fr == ref_fr
        assert got[0][-1] == eos and eos not in got[0][:-1]

    def test_max_tokens_mid_window_no_overshoot(self, devices8):
        dec = build_paged(devices8)
        for mt in (2, 3, 5, 7):
            ref, ref_fr, _ = serve(dec, PROMPTS[:2], max_tokens=mt)
            got, got_fr, _ = serve(
                dec, PROMPTS[:2], max_tokens=mt, speculate_k=4
            )
            assert got == ref and got_fr == ref_fr
            assert all(len(t) == mt for t in got)

    def test_accept_rate_zero_degenerates_to_one_token_per_step(
        self, devices8
    ):
        dec = build_paged(devices8)
        ref, ref_fr, _ = serve(dec, PROMPTS[:3])
        wrong = _WrongDrafter(ref, PROMPTS[:3], SMALL["vocab"])
        got, got_fr, eng = serve(
            dec, PROMPTS[:3], speculate_k=4, drafter=wrong
        )
        assert got == ref and got_fr == ref_fr
        s = eng.recorder.summary()
        assert s["accept_rate"] == 0.0
        assert s["tokens_per_step"] == 1.0
        assert s["drafted_tokens"] > 0

    def test_max_seq_inside_verify_window(self, devices8):
        """A slot whose remaining cache room is smaller than k gets
        a CLAMPED window (never writes past max_seq) and finishes
        "max_seq" with exactly the sequential path's tokens."""
        dec = build_paged(devices8, max_seq=16)
        prompt = [5, 9, 5, 9, 5, 9, 5]       # 7 tokens → 9 rows left
        ref, ref_fr, _ = serve(dec, [prompt], max_tokens=50)
        got, got_fr, _ = serve(
            dec, [prompt], max_tokens=50, speculate_k=4
        )
        assert got == ref and got_fr == ref_fr
        assert got_fr[0] == "max_seq"
        assert len(got[0]) == dec.max_seq - len(prompt) + 1

    def test_block_scarcity_degrades_window_before_killing(
        self, devices8
    ):
        """With the pool sized so the SEQUENTIAL run just fits, the
        speculative run must degrade its windows instead of dying
        no_blocks — same tokens, same finish reasons."""
        dec_ref = build_paged(devices8, max_slots=2, n_blocks=8)
        ref, ref_fr, _ = serve(dec_ref, PROMPTS[:2], max_tokens=8)
        dec = build_paged(devices8, max_slots=2, n_blocks=8)
        got, got_fr, _ = serve(
            dec, PROMPTS[:2], max_tokens=8, speculate_k=4
        )
        assert got == ref and got_fr == ref_fr

    def test_v1_decoder_refuses_speculation(self, devices8):
        dec = build_decoder(devices8)
        with pytest.raises(NotImplementedError, match="paged"):
            Engine(dec, speculate_k=4)

    def test_speculate_k_one_is_off(self, devices8):
        dec = build_paged(devices8)
        ref, _, _ = serve(dec, PROMPTS[:2])
        got, _, eng = serve(dec, PROMPTS[:2], speculate_k=1)
        assert got == ref
        assert eng.drafter is None
        assert eng.recorder.summary()["accept_rate"] is None


class TestTelemetry:
    def test_accept_rate_flows_through_fleet_merge(self, devices8):
        dec = build_paged(devices8)
        _, _, eng = serve(dec, PROMPTS[:4], speculate_k=4)
        s = eng.recorder.summary()
        fleet = FleetRecorder()
        fleet.attach_replica("r0", eng.recorder.state_dict())
        # a non-speculative replica merges alongside
        other = ServingRecorder(max_slots=4)
        other.record_step(
            active_slots=1, queue_depth=0, dt_s=0.01, tokens=1
        )
        fleet.attach_replica("r1", other.state_dict())
        fs = fleet.summary()
        assert fs["per_replica"]["r0"]["accept_rate"] == s["accept_rate"]
        assert fs["per_replica"]["r0"]["tokens_per_step"] > 1.0
        # fleet-wide: drafted/accepted sum across replicas
        assert fs["accept_rate"] == s["accept_rate"]
        assert fs["tokens_per_step"] is not None

    def test_state_dict_roundtrip_keeps_spec_fields(self, devices8):
        dec = build_paged(devices8)
        _, _, eng = serve(dec, PROMPTS[:2], speculate_k=4)
        r = ServingRecorder()
        r.load_state_dict(eng.recorder.state_dict())
        assert r.summary()["accept_rate"] == \
            eng.recorder.summary()["accept_rate"]

    def test_speculation_speedup_model(self):
        flat = speculation_speedup(k=4, accept_rate=0.0)
        assert flat["tokens_per_step"] == 1.0
        assert flat["speedup"] == 1.0
        full = speculation_speedup(k=4, accept_rate=1.0)
        assert full["tokens_per_step"] == 4.0
        # default: the recorder's UNCONDITIONAL accepted/drafted
        # ratio — E = 1 + a*(k-1), exact by linearity
        mid = speculation_speedup(k=4, accept_rate=0.5)
        assert mid["tokens_per_step"] == pytest.approx(2.5)
        # conditional per-draft probability: geometric
        cond = speculation_speedup(
            k=4, accept_rate=0.5, conditional=True
        )
        assert cond["tokens_per_step"] == pytest.approx(1.875)
        slow = speculation_speedup(
            k=4, accept_rate=0.5, verify_cost_ratio=1.25
        )
        assert slow["speedup"] == pytest.approx(2.5 / 1.25)

    def test_speedup_model_consistent_with_recorder_datum(
        self, devices8
    ):
        """Feeding the measured unconditional accept_rate into the
        default model must reproduce the measured tokens/step
        whenever the drafter filled full windows: tokens_per_step =
        1 + accepted/slot_steps and drafted = slot_steps*(k-1) ⇒
        E = 1 + a*(k-1) exactly."""
        dec = build_paged(devices8)
        _, _, eng = serve(dec, PROMPTS[:4], speculate_k=4)
        s = eng.recorder.summary()
        pred = speculation_speedup(k=4, accept_rate=s["accept_rate"])
        # windows can be SHORT (drafter dry, max_seq/max_tokens
        # clamps), which only lowers the measured figure
        assert s["tokens_per_step"] <= pred["tokens_per_step"] + 1e-9

    def test_measured_accept_rate_feeds_model(self, devices8):
        dec = build_paged(devices8)
        _, _, eng = serve(dec, PROMPTS[:4], speculate_k=4)
        s = eng.recorder.summary()
        pred = speculation_speedup(k=4, accept_rate=s["accept_rate"])
        # the model's expected tokens/step and the measured figure
        # describe the same machine — they must agree loosely (the
        # measured mix isn't perfectly geometric)
        assert 1.0 <= s["tokens_per_step"] <= 4.0
        assert 1.0 <= pred["tokens_per_step"] <= 4.0

    def test_occupancy_stays_bounded_under_speculation(self, devices8):
        """Multi-token steps must not inflate slot occupancy past
        1.0 (slots and tokens are separate step fields)."""
        dec = build_paged(devices8)
        _, _, eng = serve(dec, PROMPTS[:4], speculate_k=4)
        occ = eng.recorder.summary()["slot_occupancy"]
        assert occ is not None and 0.0 < occ <= 1.0


class TestSamplerRankGeneralization:
    def test_sharded_sample_shaped_equals_flat(self, devices8):
        """The public sampler's higher-rank branch ([S, k, V/tp]
        rows, the verify-step shape): shaped input samples each row
        exactly as the flat batch does — bitwise, greedy and
        temperature, tp=1 and tp=2."""
        import numpy as np

        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        from theanompi_tpu.parallel import MODEL_AXIS, make_mesh
        from theanompi_tpu.parallel import tp as tp_lib

        V, S, K = 64, 3, 4
        rng = np.random.default_rng(9)
        logits = rng.normal(size=(S, K, V)).astype(np.float32)
        keys = np.stack([
            np.asarray(jax.random.PRNGKey(i), np.uint32)
            for i in range(S * K)
        ]).reshape(S, K, 2)
        temps = np.array(
            [[0.0, 0.9, 0.7, 0.0]] * S, np.float32
        )

        def run(tp, lg, ks, ts, spec_lg):
            mesh = make_mesh(
                data=1, model=tp, devices=devices8[:tp]
            )
            fn = jax.jit(jax.shard_map(
                lambda a, b, c: tp_lib.sharded_sample(a, V, b, c),
                mesh=mesh,
                in_specs=(spec_lg, P(), P()),
                out_specs=P(),
                check_vma=False,
            ))
            return np.asarray(fn(
                jnp.asarray(lg, jnp.float32),
                jnp.asarray(ks, jnp.uint32),
                jnp.asarray(ts, jnp.float32),
            ))

        for tp in (1, 2):
            flat = run(
                tp, logits.reshape(S * K, V), keys.reshape(-1, 2),
                temps.reshape(-1), P(None, MODEL_AXIS),
            )
            shaped = run(
                tp, logits, keys, temps, P(None, None, MODEL_AXIS),
            )
            assert shaped.shape == (S, K)
            assert shaped.reshape(-1).tolist() == flat.tolist()
