"""The hybrid state-space / attention decoder (``Llama`` with
``layer_types`` of ``"mamba"`` and ``"attention"``, no rotary
position, four multipliers, a tied head) against its plain reference
(``benchmark/reference/granite_hybrid.py``) on the CPU in float32, at
the small sizes the benchmark's configuration keeps under
``rehearsal``: loss, first-sequence logits and every leaf's gradient;
every wrong build of ``benchmark/tools/granite_check.py`` fails
there; the tied leaf's gradient; the vocabulary slice; the cut
pattern; the refusals."""

import json
from pathlib import Path

import jax
import numpy as np
import pytest

from benchmark.tools import granite_check
from theanompi_tpu.models.llama import Llama

ROOT = Path(__file__).resolve().parents[1]
CELL = "granite4h_micro_train_t8192"
SEED = 11


@pytest.fixture(scope="module")
def held():
    """The right program and every wrong build, each held to the
    reference once (``granite_check.check`` at the rehearsal sizes)."""
    with jax.default_matmul_precision("highest"):
        return granite_check.check(
            CELL, SEED, list(granite_check.VARIANTS), rehearsal=True,
            control=True)


def test_program_equals_the_reference_in_float32(held):
    right = held["right"]
    assert right["loss_rel"] < 1e-6
    assert right["logits_rel"] < 1e-5
    # every leaf, the tied matrix as one
    assert right["grad_rel_worst"] < 1e-3, right["grad_rel_worst_leaf"]
    assert len(right["grad_rel"]) == 1 + 1 + 9 * 13 + 9
    assert "lm_head" not in right["grad_rel"]
    assert held["ok"]


def test_scan_counters_say_the_carry_is_alive(held):
    right = held["right"]
    assert len(right["ssm_state_rms"]) == 9
    assert min(right["ssm_state_rms"]) > 0
    assert max(right["ssm_log_decay_min"]) < 0
    assert held["variants"]["no_state_carry"]["ssm_state_rms"] == [0.0] * 9


@pytest.mark.parametrize("variant", sorted(granite_check.VARIANTS))
def test_a_wrong_build_fails_a_limit(held, variant):
    got = held["variants"][variant]
    assert not got["ok"], got
    assert held["failed"][variant]


def test_the_loss_alone_tells_few_of_them_apart(held):
    """At initialisation the loss is ln(V) whatever the architecture
    (PRs 26, 37 and 41 found so): the gradients must tell."""
    same_loss = [
        n for n, got in held["variants"].items()
        if n in granite_check.VARIANTS
        and got["loss_rel"] <= granite_check.LOSS_RTOL
    ]
    assert {"untied_head", "no_state_carry", "rope_on_attention",
            "sm_scale_sqrt", "pattern_shifted"} <= set(same_loss)
    for name in same_loss:
        assert (held["variants"][name]["grad_rel_worst"]
                > granite_check.GRAD_RTOL)


def test_the_reference_in_a_lower_precision_fails_too(held):
    assert held["failed"][granite_check.CONTROL]


def _small(**over):
    from benchmark.drivers.train import program_config
    from benchmark.run import load_cell

    config = load_cell(CELL)["config"]
    config = dict(config, **config["rehearsal"])
    return dict(program_config(config, seed=SEED, n_replicas=1),
                optimizer="sgd", device_data_cache=False, **over)


def _start():
    from theanompi_tpu.parallel import make_mesh

    model = Llama(_small())
    model.build_model(n_replicas=1)
    model.compile_iter_fns(mesh=make_mesh(data=1, devices=jax.devices()[:1]))
    p0 = jax.tree.map(np.asarray, jax.device_get(model.params))
    model.data.shuffle(0)
    batch = tuple(np.asarray(a) for a in model.data.train_batch(0))
    return model, p0, batch


def test_the_tied_leafs_gradient_is_the_lookups_plus_the_heads():
    from benchmark.run import load_cell

    config = load_cell(CELL)["config"]
    model, p0, batch = _start()
    with jax.default_matmul_precision("highest"):
        _, tied, *_ = granite_check._program_step(
            config, _small(), None, p0, batch)
        _, apart, *_ = granite_check._program_step(
            config, _small(tie_word_embeddings=False), None,
            dict(p0, lm_head=np.ascontiguousarray(p0["embed"].T)), batch)
    assert float(np.abs(apart["lm_head"]).max()) > 0
    np.testing.assert_allclose(
        tied["embed"], apart["embed"] + apart["lm_head"].T,
        rtol=1e-4, atol=1e-4 * float(np.abs(tied["embed"]).max()))
    # every other leaf is what it was
    np.testing.assert_allclose(
        tied["layers"][0]["ssm_in"], apart["layers"][0]["ssm_in"],
        rtol=1e-4, atol=1e-7)


def test_a_sliced_vocabulary_is_a_smaller_vocabulary():
    """ids, logits and loss run over the rows the model holds, and ONE
    matrix is embedding and head."""
    model, p0, (x, y) = _start()
    v = model.vocab
    assert p0["embed"].shape == (v, model.dim) and "lm_head" not in p0
    assert int(x.max()) < v and int(y.max()) < v
    spec = model._batch_sharding.spec
    logits = jax.jit(jax.shard_map(
        lambda p, ids: model._forward(p, ids), mesh=model.mesh,
        in_specs=(model._specs, spec), out_specs=jax.P(*spec, "model"),
    ))(model.params, x)
    assert logits.shape == (*x.shape, v)
    loss = float(model._train_step(
        model.params, model.opt_state, model.ef_state,
        *model.put_batch((x, y)), np.float32(0.0))[3])
    assert abs(loss - np.log(v)) < 0.05 * np.log(v)


def test_a_cut_stack_keeps_the_first_entries_of_the_pattern():
    published = json.loads(
        (ROOT / "benchmark/configs/granite_4.0_h_micro_train_l10.json")
        .read_text())
    types = published["layer_types"]
    assert len(types) == published["published"]["num_hidden_layers"] == 40
    model = Llama(_small())
    assert model.mixer_kinds == tuple(types[:10])
    assert model.mixer_kinds_count == {"attention": 1, "mamba": 9}
    assert model.attention_kinds == {"full_attention": 1}
    assert model.ssd_chunk == 8
    cut = Llama(_small(n_layers=7))
    assert cut.mixer_kinds == tuple(types[:7])
    # nine to one in every period of the published forty
    for start in range(0, 40, 10):
        assert types[start:start + 10].count("attention") == 1
    with pytest.raises(ValueError, match="unknown \\['mamba2'\\]"):
        Llama(_small(layer_types=["mamba2"] * 10))


def test_defaults_leave_an_older_decoder_as_it_was():
    plain = Llama({})
    assert plain.mixer_kinds == ("attention",) * plain.n_layers
    assert (plain.embedding_multiplier, plain.residual_multiplier,
            plain.logits_scaling, plain.attention_multiplier) == (
        1.0, 1.0, 1.0, None)
    assert not plain.tie_word_embeddings
    assert plain.position_embedding_type == "rope"
    assert plain.ssd_chunk is None
    assert "lm_head" in plain.param_specs()


MAMBA_REFUSAL = "a mamba layer or block (layer_types) does not yet compose"
REFUSED = [
    (dict(tp=2), MAMBA_REFUSAL),
    (dict(attention="mla", q_lora_rank=8, kv_lora_rank=8,
          qk_nope_head_dim=4, qk_rope_head_dim=4, v_head_dim=8),
     MAMBA_REFUSAL),
    (dict(layer_types=["mamba", "sliding_attention"] * 5, sliding_window=4),
     MAMBA_REFUSAL),
    (dict(mtp_depth=1), MAMBA_REFUSAL),
    # the same stack as BLOCKS of one sublayer (``layer_types`` a
    # pattern: a mixer or an attention alone) refuses what blocks do
    # not run
    (dict(layer_types="M*" * 5, tp=2), MAMBA_REFUSAL),
    (dict(layer_types="M*" * 5, mtp_depth=1),
     "a block pattern (layer_types as a string"),
    (dict(sp=2), "does not yet compose with pipeline parallelism, sequence"),
    (dict(pp=2), "does not yet compose with pipeline parallelism, sequence"),
    (dict(ut_steps=2),
     "does not yet compose with pipeline parallelism, sequence"),
]


@pytest.mark.parametrize("over, sentence", REFUSED, ids=[
    "tp", "mla", "window", "mtp", "blocks_tp", "blocks_mtp", "sp", "pp",
    "ut_steps"])
def test_a_mamba_stack_refuses_what_it_has_not_been_run_with(over, sentence):
    with pytest.raises(NotImplementedError) as e:
        Llama(_small(**over))
    assert sentence in str(e.value)
    assert sentence.split(" (")[0] in (ROOT / "docs/REFUSALS.md").read_text()


PLAIN = dict(dim=32, n_layers=2, n_heads=4, n_kv_heads=2, ffn_dim=64,
             vocab=64, seq_len=16, batch_size=2)


@pytest.mark.parametrize("over, sentence", [
    (dict(position_embedding_type="nope", attention="mla", q_lora_rank=8,
          kv_lora_rank=8, qk_nope_head_dim=4, qk_rope_head_dim=4,
          v_head_dim=8),
     "position_embedding_type: nope is grouped-query attention's"),
    (dict(logits_scaling=8, mtp_depth=1),
     "logits_scaling does not yet compose with a multi-token-prediction"),
    (dict(tie_word_embeddings=True, pp=2),
     "tie_word_embeddings does not yet compose with pipeline"),
    (dict(residual_multiplier=0.22, sp=2),
     "a multiplier (embedding_multiplier, residual_multiplier"),
    (dict(position_embedding_type="nope", ut_steps=2),
     "position_embedding_type: nope does not yet compose with pipeline"),
], ids=["nope_mla", "scaling_mtp", "tied_pp", "multiplier_sp", "nope_ut"])
def test_the_other_new_knobs_refuse_too(over, sentence):
    with pytest.raises(NotImplementedError) as e:
        Llama(dict(PLAIN, **over))
    assert sentence in str(e.value)
    doc = (ROOT / "docs/REFUSALS.md").read_text()
    assert (sentence in doc
            or "does not yet compose with pipeline parallelism" in doc)


def test_serving_refuses_a_stack_with_recurrent_state():
    model, _, _ = _start()
    with pytest.raises(NotImplementedError) as e:
        model.make_decoder(max_slots=2, max_seq=32)
    assert "serving has no recurrent state" in str(e.value)
    assert "serving has no recurrent state" in (
        ROOT / "docs/REFUSALS.md").read_text()


def test_serving_refuses_a_tied_head_without_a_mamba_layer():
    from theanompi_tpu.parallel import make_mesh

    model = Llama(dict(PLAIN, tie_word_embeddings=True, optimizer="sgd"))
    model.build_model(n_replicas=1)
    model.compile_iter_fns(mesh=make_mesh(data=1, devices=jax.devices()[:1]))
    with pytest.raises(NotImplementedError, match="a tied head"):
        model.make_decoder(max_slots=2, max_seq=16)


def test_the_summary_says_which_form_of_the_scan_ran(monkeypatch):
    """``"ssd_kernel"`` beside ``"ssd_chunk"``: the tiles the scan's
    kernels took, ``{}`` where XLA's form runs — here, off the TPU;
    ``Llama.ssd_kernel()`` answers from shapes and the device alone,
    before anything is built."""
    from benchmark.drivers.train import program_config
    from benchmark.run import load_cell
    from theanompi_tpu.ops import attention
    from theanompi_tpu.workers import bsp_worker

    assert Llama(_small()).ssd_kernel() == {}
    assert Llama({}).ssd_kernel() == {}         # no mamba layer
    res = bsp_worker.run(
        devices=[0], modelfile="theanompi_tpu.models.llama",
        modelclass="Llama", verbose=False,
        config=dict(_small(), n_train=2, n_val=1, n_epochs=1),
    )
    assert res["ssd_kernel"] == {} and res["ssd_chunk"] == 8
    # at the published widths on a TPU: 16 of the 64 heads a grid
    # step, the mask in blocks of 128 positions
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    published = Llama(program_config(
        load_cell(CELL)["config"], seed=SEED, n_replicas=1))
    assert published.ssd_kernel() == dict(chunk=256, heads=16, sub=128)
    # the rehearsal's sizes tile nothing: XLA's form there too
    assert Llama(_small()).ssd_kernel() == {}
