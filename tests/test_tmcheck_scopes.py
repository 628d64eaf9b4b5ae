"""tmcheck rule family 5 — TM107 profiler-scope registration
(``theanompi_tpu/analysis/scopes.py``; ISSUE 15 satellite).

The failure mode under test: a ``jax.named_scope`` label absent from
``analysis/registry.PROFILE_SCOPES``/``PROFILE_SCOPE_PREFIXES`` looks
instrumented but the step-phase profiler silently files its ops under
the unscoped-compute leg.  Fixtures: positive + clean twin per shape
(literal, f-string family, dynamic), suppression semantics, and the
registry↔profiler coupling."""

import textwrap

from theanompi_tpu.analysis import core, scopes
from theanompi_tpu.analysis.registry import (
    PROFILE_SCOPE_PREFIXES,
    PROFILE_SCOPES,
)


def run(src: str) -> list:
    sf = core.SourceFile(textwrap.dedent(src), "fixture.py")
    return core.collect([sf], rule_fns=(scopes.check_file,))


class TestTM107:
    def test_unregistered_literal_flagged(self):
        out = run("""
            import jax

            def step(x):
                with jax.named_scope("my_new_phase"):
                    return x * 2
        """)
        assert [f.rule for f in out] == ["TM107"]
        assert "my_new_phase" in out[0].message
        assert "unscoped-compute" in out[0].message

    def test_registered_literal_clean_twin(self):
        out = run("""
            import jax

            def step(x):
                with jax.named_scope("opt_update"):
                    return x * 2
        """)
        assert out == []

    def test_registered_prefix_literal_clean(self):
        out = run("""
            import jax

            def step(x):
                with jax.named_scope("exchange_b3"):
                    return x
        """)
        assert out == []

    def test_fstring_on_registered_prefix_clean(self):
        out = run("""
            import jax

            def step(xs):
                for i, x in enumerate(xs):
                    with jax.named_scope(f"exchange_b{i}"):
                        pass
        """)
        assert out == []

    def test_fstring_unregistered_head_flagged(self):
        out = run("""
            import jax

            def step(xs):
                for i, x in enumerate(xs):
                    with jax.named_scope(f"mystery_{i}"):
                        pass
        """)
        assert [f.rule for f in out] == ["TM107"]

    def test_fstring_short_head_flagged(self):
        """A literal head that is merely a PREFIX of a registered
        prefix (f"e{i}", f"exchange_{x}") must flag: the profiler's
        label regex needs the full prefix + digits, so these labels
        would land in the unscoped-compute leg (review finding)."""
        for head in ("e", "exchange_"):
            out = run(f"""
                import jax

                def step(xs):
                    for i, x in enumerate(xs):
                        with jax.named_scope(f"{head}{{i}}"):
                            pass
            """)
            assert [f.rule for f in out] == ["TM107"], head

    def test_dynamic_label_flagged(self):
        out = run("""
            import jax

            def step(x, label):
                with jax.named_scope(label):
                    return x
        """)
        assert [f.rule for f in out] == ["TM107"]
        assert "not a (f-)string literal" in out[0].message

    def test_bare_named_scope_import_checked(self):
        out = run("""
            from jax import named_scope

            def step(x):
                with named_scope("rogue"):
                    return x
        """)
        assert [f.rule for f in out] == ["TM107"]

    def test_suppression_silences_and_tracks(self):
        out = run("""
            import jax

            def step(x):
                with jax.named_scope("rogue"):  # tmcheck: disable=TM107
                    return x
        """)
        assert out == []
        stale = run("""
            import jax

            def step(x):
                with jax.named_scope("opt_update"):  # tmcheck: disable=TM107
                    return x
        """)
        assert [f.rule for f in stale] == ["TM201"]

    def test_unrelated_calls_ignored(self):
        out = run("""
            def step(x):
                return scope("anything") + named("x")
        """)
        assert out == []

    def test_tests_are_not_exempt(self):
        """Unlike the hot-path seeds, a scope minted inside a test_*
        function still needs registration — same attribution path."""
        out = run("""
            import jax

            def test_something():
                with jax.named_scope("fixture_only"):
                    pass
        """)
        assert [f.rule for f in out] == ["TM107"]


class TestRegistryProfilerCoupling:
    def test_every_registered_label_resolves(self):
        for label in PROFILE_SCOPES:
            assert scopes.label_registered(label)
        for prefix in PROFILE_SCOPE_PREFIXES:
            assert scopes.label_registered(prefix + "0")

    def test_profiler_attributes_registered_labels(self):
        """The registry the RULE enforces is the one the PROFILER
        reads: every exact label extracts into its registered leg."""
        from theanompi_tpu.obs.profiler import profile_scope_sets

        hlo = "\n".join(
            f'  %op.{i} = f32[2] add(...), '
            f'metadata={{op_name="jit(f)/{label}/add"}}'
            for i, label in enumerate(sorted(PROFILE_SCOPES))
        )
        sets = profile_scope_sets(hlo)
        assert set(sets) == set(PROFILE_SCOPES.values())

    def test_rule_in_catalog(self):
        assert "TM107" in core.RULES


class TestBlockScopes:
    """The step program's block names (PR 35): registered, and the
    files that carry them clean under TM107."""

    BLOCKS = ("blk_embed", "blk_attn", "blk_ffn", "blk_head",
              "blk_conv", "blk_bn", "blk_pool", "blk_mtp_in", "blk_ssm")

    def test_every_block_label_is_registered_under_its_own_leg(self):
        for label in self.BLOCKS:
            assert scopes.label_registered(label), label
            assert PROFILE_SCOPES[label] == label

    def test_the_files_that_name_blocks_are_clean(self):
        import pathlib
        import re

        root = pathlib.Path(__file__).resolve().parents[1]
        used = set()
        for rel in ("theanompi_tpu/models/llama.py",
                    "theanompi_tpu/models/base.py",
                    "theanompi_tpu/ops/layers.py",
                    "theanompi_tpu/ops/ssd.py"):
            src = (root / rel).read_text()
            used |= set(re.findall(r'named_scope\("(blk_\w+)"\)', src))
            out = core.collect([core.SourceFile(src, rel)],
                               rule_fns=(scopes.check_file,))
            assert out == [], "\n".join(str(f) for f in out)
        assert used == set(self.BLOCKS)
