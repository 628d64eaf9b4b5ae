"""What the per-layer remat keeps, and that keeping it skips a kernel.

A layer's attention needs two kernels: forward and backward.  Under
``jax.checkpoint`` with no policy the backward runs a third, the
forward kernel again, because the backward rule of ``_flash`` reads
the forward rule's own ``out`` and ``lse``.  ``ops/attention.py``
names those two (``FLASH_RESIDUALS``) and ``Llama._forward`` saves
them, so the replay holds no kernel.  Counted here in the gradient's
jaxpr (``pallas_call`` equations; the kernels themselves run in the
Pallas interpreter), for a bare layer function and for the model's
own train step; ``tests/test_chip_compile.py`` counts the same in a
text compiled for the chip.


The same remat keeps, for the LAST ``remat_kept_calls`` dense layer
calls, the MLP's gate and up products (``ops.layers.MLP_RESIDUALS``)
and, for the last ``remat_kept_attn_calls`` grouped-query attention
calls, q, k, v and the attention block's output
(``models.llama.ATTN_RESIDUALS``): as many calls as
``Llama.remat_keep_calls`` finds room for between its estimate of the
step's peak and the device's memory, the MLP's copies first.  The
second half of this file counts the replayed products, holds
all-kept against none-kept, and pins the rule and the estimate.

A dropless expert call keeps, the same way and from what the other two
sets leave, its sorted rows, their gate and up products and the sort's
two results (``parallel.moe.MOE_RESIDUALS``): all ``k N`` rows where
every expert is here, under a held range the first window's ``R``.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.ad_checkpoint import checkpoint_name

from theanompi_tpu.models import llama
from theanompi_tpu.models.llama import ATTN_RESIDUALS, Llama
from theanompi_tpu.ops import attention
from theanompi_tpu.ops.attention import FLASH_RESIDUALS, flash_attention_tpu
from theanompi_tpu.ops.layers import MLP_RESIDUALS
from theanompi_tpu.parallel import make_mesh, moe
from theanompi_tpu.parallel.moe import MOE_RESIDUALS

B, H, T, D = 2, 2, 32, 16


def _eqns(jaxpr):
    """Every equation of a jaxpr, sub-jaxprs opened."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub)


def _count(jaxpr, primitive="pallas_call"):
    """Equations of ``primitive`` in a jaxpr, sub-jaxprs opened."""
    return sum(eqn.primitive.name == primitive for eqn in _eqns(jaxpr))


def _layer(causal, t_k, name_after_call=None):
    """A stand-in for a decoder block: projections from ``x``, the
    flash kernel, an output projection.  K and V come from the first
    ``t_k`` positions."""
    def layer(w, x):
        def heads(a):
            return a.reshape(B, -1, H, D).transpose(0, 2, 1, 3)

        q, k, v = heads(x @ w), heads(x[:, :t_k] @ (0.5 * w)), \
            heads(x[:, :t_k] @ (2.0 * w))
        o = flash_attention_tpu(
            q, k, v, causal=causal, block_q=16, block_k=16, interpret=True
        )
        if name_after_call:
            o = checkpoint_name(o, name_after_call)
        return jnp.tanh(o.transpose(0, 2, 1, 3).reshape(B, T, H * D) @ w)

    return layer


def _grad(layer, policy):
    return jax.grad(
        lambda w, x: jax.checkpoint(layer, policy=policy)(w, x).sum(),
        argnums=(0, 1),
    )


def _inputs():
    kw, kx = jax.random.split(jax.random.key(0))
    return (0.2 * jax.random.normal(kw, (H * D, H * D)),
            jax.random.normal(kx, (B, T, H * D)))


def _model_policy():
    return jax.checkpoint_policies.save_only_these_names(
        *Llama(dict(remat=True)).remat_saves
    )


CASES = pytest.mark.parametrize(
    "causal,t_k", [(True, T), (False, T), (True, T // 2), (False, T // 2)],
    ids=["causal-equal", "full-equal", "causal-short_k", "full-short_k"],
)


@CASES
def test_policy_drops_the_replayed_forward_kernel(causal, t_k):
    layer, args = _layer(causal, t_k), _inputs()
    kept = jax.make_jaxpr(_grad(layer, _model_policy()))(*args)
    full = jax.make_jaxpr(_grad(layer, None))(*args)
    assert (_count(kept.jaxpr), _count(full.jaxpr)) == (2, 3)


@CASES
def test_gradient_is_bitwise_full_remats(causal, t_k):
    layer, args = _layer(causal, t_k), _inputs()
    kept = jax.jit(_grad(layer, _model_policy()))(*args)
    full = jax.jit(_grad(layer, None))(*args)
    for a, b in zip(kept, full):
        assert np.array_equal(np.asarray(a), np.asarray(b))
        assert float(jnp.abs(a).max()) > 0


def test_a_name_after_the_call_skips_nothing():
    """The knob this replaced (``remat_save=("attn_out",)``) named the
    layer's copy of the output: the array is saved and the kernel
    still runs again, for the logsumexp.  Do not put it back."""
    layer, args = _layer(True, T, name_after_call="attn_out"), _inputs()
    policy = jax.checkpoint_policies.save_only_these_names("attn_out")
    assert _count(jax.make_jaxpr(_grad(layer, policy))(*args).jaxpr) == 3
    both = jax.checkpoint_policies.save_only_these_names(
        "attn_out", *FLASH_RESIDUALS
    )
    assert _count(jax.make_jaxpr(_grad(layer, both))(*args).jaxpr) == 2


TINY = dict(
    dim=32, n_heads=2, n_kv_heads=1, ffn_dim=64, vocab=32, seq_len=T,
    batch_size=2, n_train=8, n_val=4, compute_dtype="float32",
)


@pytest.mark.parametrize("remat", [True, False])
def test_model_says_what_its_remat_keeps(remat):
    # the key of the knob this replaced is read by nothing
    model = Llama(dict(TINY, n_layers=1, remat=remat,
                       remat_save=("attn_out",)))
    assert model.remat_saves == (FLASH_RESIDUALS if remat else ())


@pytest.mark.parametrize(
    "over,per_layer",
    [({}, 2), ({"remat": False}, 2), ({"saves": ()}, 3),
     ({"n_experts": 4, "moe_top_k": 2, "capacity_factor": None}, 2)],
    ids=["remat", "no_remat", "policy_bypassed", "moe"],
)
def test_train_step_runs_two_flash_kernels_a_layer(
    monkeypatch, over, per_layer
):
    """The model's own train step, traced with the kernel path taken
    as on the chip (nothing is lowered, so no kernel compiles)."""
    over = dict(over)
    saves = over.pop("saves", None)
    n_layers = 2
    model = Llama(dict(TINY, n_layers=n_layers, **over))
    if saves is not None:
        model.remat_saves = saves       # full remat: what the parent ran
    model.build_model(n_replicas=1)
    model.compile_iter_fns(mesh=make_mesh(data=1, devices=jax.devices()[:1]))
    x, y = model.put_batch(model.data.train_batch(0))
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    jaxpr = jax.make_jaxpr(model.train_step_fn)(
        model.params, model.opt_state, model.ef_state, x, y,
        jnp.float32(model.current_lr),
    )
    assert _count(jaxpr.jaxpr) == per_layer * n_layers


@pytest.mark.parametrize("remat", [True, False])
def test_worker_summary_names_what_remat_keeps(remat):
    from theanompi_tpu.workers import bsp_worker

    res = bsp_worker.run(
        devices=[0], modelfile="theanompi_tpu.models.llama",
        modelclass="Llama",
        config=dict(TINY, n_layers=1, n_epochs=1, remat=remat, seed=3),
        verbose=False,
    )
    assert res["remat_saves"] == (list(FLASH_RESIDUALS) if remat else [])
    # the CPU reports no memory limit: no call keeps more
    assert (res["remat_kept_calls"], res["remat_kept_attn_calls"],
            res["remat_kept_moe_calls"]) == (0, 0, 0)


def test_worker_summary_of_a_model_without_layer_remat():
    from theanompi_tpu.workers import bsp_worker

    res = bsp_worker.run(
        devices=[0], modelfile="theanompi_tpu.models.wresnet",
        modelclass="WResNet",
        config={"batch_size": 4, "depth": 10, "widen": 1, "n_train": 4,
                "n_val": 4, "seed": 7, "n_epochs": 1},
        verbose=False,
    )
    assert res["remat_saves"] == []
    assert (res["remat_kept_attn_calls"], res["remat_kept_bytes"]) == (0, 0)


# -- the MLP's two products, kept for the calls the memory holds -------------

GIB = 1 << 30
FFN = TINY["ffn_dim"]


def _replayed_mlp_products(jaxpr):
    """Products with an ``[.., ffn_dim]`` result in the remat's replay
    (``rematted_computation`` in the equation's name stack): the gate
    and the up projection of a call that keeps neither."""
    return sum(
        eqn.primitive.name == "dot_general"
        and eqn.outvars[0].aval.shape[-1] == FFN
        and "rematted_computation" in str(eqn.source_info.name_stack)
        for eqn in _eqns(jaxpr)
    )


def _step_model(n_keep, tp=1, n_keep_attn=0, n_keep_moe=0, **over):
    """A TINY model with its step built and ``n_keep`` (calls that
    keep ``MLP_RESIDUALS``), ``n_keep_attn`` (``ATTN_RESIDUALS``) and
    ``n_keep_moe`` (``MOE_RESIDUALS``) forced (the CPU reports no
    memory limit: ``compile_iter_fns`` leaves 0 of each)."""
    model = Llama(dict(TINY, n_layers=2, optimizer="sgd", lr=1.0, tp=tp,
                       n_kv_heads=tp, **over))
    model.build_model(n_replicas=1)
    model.compile_iter_fns(
        mesh=make_mesh(data=1, model=tp, devices=jax.devices()[:tp]))
    assert (model.remat_kept_calls, model.remat_kept_attn_calls,
            model.remat_kept_moe_calls) == (0, 0, 0)
    model.remat_kept_moe_calls = (
        model.remat_calls if n_keep_moe == "all" else n_keep_moe)
    model.remat_kept_calls = (
        model.remat_calls if n_keep == "all" else n_keep)
    model.remat_kept_attn_calls = (
        model.remat_calls if n_keep_attn == "all" else n_keep_attn)
    return model


def _step_args(model):
    x, y = model.put_batch(model.data.train_batch(0))
    return (model.params, model.opt_state, model.ef_state, x, y,
            jnp.float32(model.current_lr))


DECODERS = pytest.mark.parametrize(
    "over", [{}, {"ut_steps": 4, "exit_beta": 0.1}], ids=["plain", "looped"])


@DECODERS
@pytest.mark.parametrize("n_keep", [0, 1, "all"])
def test_kept_calls_replay_no_gate_or_up_product(n_keep, over):
    model = _step_model(n_keep, **over)
    jaxpr = jax.make_jaxpr(model.train_step_fn)(*_step_args(model))
    replayed = model.remat_calls - model.remat_kept_calls
    assert model.remat_calls == 2 * over.get("ut_steps", 1)
    assert _replayed_mlp_products(jaxpr.jaxpr) == 2 * replayed


# where grouped-query attention puts its names: after the rotation,
# on the products' outputs under QK-norm, and without a rotation
ATTENTIONS = {"rope": {}, "qk_norm": {"qk_norm": True},
              "nope": {"position_embedding_type": "nope"}}


def _replayed_attn_products(jaxpr, model):
    """(q / k / v products, ``wo`` products) in the remat's replay of
    a TINY model's step: under ``gqa_proj`` the calls that take the
    normed ``[B, T, D]`` input (``tp.col_parallel_heads``: the
    rotation and QK-norm take ``[B, h, T, hd]``), under ``blk_attn``
    beside them the one product with a ``[B, T, D]`` result (the
    reference attention's two have four axes)."""
    row = (TINY["batch_size"], T, model.dim)
    proj = wo = 0
    for eqn in _eqns(jaxpr):
        stack = str(eqn.source_info.name_stack)
        if "rematted_computation" not in stack or "blk_attn" not in stack:
            continue
        if "gqa_proj" in stack:
            proj += (eqn.primitive.name == "custom_vjp_call"
                     and any(v.aval.shape == row for v in eqn.invars))
        else:
            wo += (eqn.primitive.name == "dot_general"
                   and eqn.outvars[0].aval.shape == row)
    return proj, wo


LOOPED = DECODERS.args[1][1]
# plain and looped decoders under each (a looped stack does not
# compose with ``nope``)
ATTN_DECODERS = pytest.mark.parametrize("over", [
    *ATTENTIONS.values(), LOOPED, dict(LOOPED, **ATTENTIONS["qk_norm"]),
], ids=[*ATTENTIONS, "looped", "looped-qk_norm"])


@ATTN_DECODERS
@pytest.mark.parametrize("n_keep", [0, 1, "all"])
def test_kept_attention_calls_replay_no_projection(n_keep, over):
    """A call that keeps ``ATTN_RESIDUALS`` replays none of its four
    products, a call that does not replays all four; the MLP's two
    are replayed either way (no call keeps those here)."""
    model = _step_model(0, n_keep_attn=n_keep, **over)
    jaxpr = jax.make_jaxpr(model.train_step_fn)(*_step_args(model))
    replayed = model.remat_calls - model.remat_kept_attn_calls
    assert model.remat_calls == 2 * over.get("ut_steps", 1)
    assert _replayed_attn_products(jaxpr.jaxpr, model) == (
        3 * replayed, replayed)
    assert _replayed_mlp_products(jaxpr.jaxpr) == 2 * model.remat_calls


@pytest.mark.parametrize("over, kept_attn, mlp, attn, experts", [
    (dict(n_layers=3), 1, {2}, {2}, set()),
    (dict(n_layers=2, ut_steps=4, exit_beta=0.1), 3, {7}, {5, 6, 7}, set()),
    # the dense calls, the attention calls and the expert calls are
    # not the same calls
    (dict(n_layers=3, n_experts=4, moe_top_k=2, first_k_dense=1), 2,
     {0}, {1, 2}, {2}),
    (dict(n_layers=3, layer_types=["attention", "mamba", "mamba"],
          mamba_n_heads=4, mamba_d_head=16, mamba_d_state=16,
          mamba_chunk_size=16), 1, {2}, {0}, set()),
], ids=["plain", "looped", "first_dense", "hybrid"])
def test_kept_calls_are_the_last_of_their_kind(over, kept_attn, mlp, attn,
                                               experts):
    model = Llama(dict(TINY, **over))
    model.remat_kept_calls, model.remat_kept_attn_calls = 1, kept_attn
    model.remat_kept_moe_calls = 1
    assert model._kept_calls() == (mlp, attn, experts)


# an expert layer with every expert here (all ``k N`` sorted rows at
# once) and one that holds 2 of 8 (192 picks in windows of 96 rows)
EXPERTS = {
    "moe": dict(n_experts=4, moe_top_k=2, capacity_factor=None),
    "moe_held": dict(n_experts=8, moe_top_k=3, capacity_factor=None,
                     moe_experts_held=2),
}
EXPERT_DECODERS = pytest.mark.parametrize(
    "over", EXPERTS.values(), ids=EXPERTS)


@pytest.mark.parametrize(
    "over", [{}, *EXPERTS.values(), ATTENTIONS["qk_norm"],
             ATTENTIONS["nope"]],
    ids=["dense", *EXPERTS, "qk_norm", "nope"])
def test_names_alone_leave_the_lowered_step_as_it_was(monkeypatch, over):
    """No call kept: ONE policy, the parent's; ``checkpoint_name``
    lowers to nothing, so the step's text is the text without the
    MLP's two names, attention's four and the expert layer's five (an
    expert layer has attention's and its own)."""
    def text():
        model = _step_model(0, **over)
        text = model._train_step.lower(*_step_args(model)).as_text()
        # (the lowering numbers its private functions as it meets them)
        return re.sub(r"@(\w+?)_\d+\b", r"@\1", text)

    named = text()
    for module in (llama, moe):
        monkeypatch.setattr(module, "checkpoint_name", lambda x, name: x)
    assert named == text()


def _loss_and_grads(model, compiler_options=None):
    """A step's loss and its gradients (plain SGD at lr 1: the step's
    parameter change)."""
    before = jax.tree.map(np.asarray, model.params)
    args = _step_args(model)
    step = model._train_step.lower(*args).compile(
        compiler_options=compiler_options)
    params, _, _, loss, *_ = step(*args)
    grads = jax.tree.map(lambda a, b: a - np.asarray(b), before, params)
    return float(loss), grads


def _assert_bitwise(kept, none, leaves):
    (loss_kept, kept), (loss_none, none) = kept, none
    assert loss_kept == loss_none
    for a, b in zip(jax.tree.leaves(kept), jax.tree.leaves(none)):
        assert np.array_equal(a, b)
    for leaf in leaves:
        assert float(np.abs(kept["layers"][0][leaf]).max()) > 0


@DECODERS
@pytest.mark.parametrize("tp", [1, 2])
def test_all_calls_kept_is_bitwise_none_kept(tp, over):
    """Loss and gradients with every call's products kept against
    every call's replayed."""
    _assert_bitwise(
        *(_loss_and_grads(_step_model(n_keep, tp=tp, **over))
          for n_keep in ("all", 0)),
        leaves=["w_gate"])


@ATTN_DECODERS
@pytest.mark.parametrize("tp", [1, 2])
def test_all_attention_calls_kept_is_bitwise_none_kept(tp, over):
    """The same with every call's q, k, v and block output kept
    against every call's rebuilt: the same products in the same
    precision, run once instead of twice.  Both steps are compiled as
    they are written: left to itself XLA:CPU fuses the replayed
    residual add into its neighbours otherwise than the forward's,
    and an ``mlp_norm`` gradient then differs in its last bit."""
    _assert_bitwise(
        *(_loss_and_grads(
            _step_model(0, tp=tp, n_keep_attn=n_keep_attn, **over),
            {"xla_backend_optimization_level": 0})
          for n_keep_attn in ("all", 0)),
        leaves=["wq", "wk", "wv", "wo"])


def _replayed_expert_work(jaxpr, model):
    """(gathers of the layer's sorted rows, gate / up grouped products,
    sorts) in the remat's replay of a TINY expert model's step, loops
    and custom rules opened: ``[R, D]`` gathers, ``[R, ffn_dim]``
    grouped products and the two sorts of the ``k N`` picks under
    ``rematted_computation`` — with all experts here at the replay's
    top, under a held range in the forward loop it runs again.  (A
    sub-jaxpr's name stacks are relative to the equation that holds
    it.)"""
    picks = model.moe_top_k * TINY["batch_size"] * T
    rows = moe.held_rows_bound(picks, model.moe_experts_held,
                               model.n_experts)
    found = {"gather": 0, "ragged_dot_general": 0, "sort": 0}
    shapes = {"gather": (rows, model.dim), "ragged_dot_general": (rows, FFN),
              "sort": (picks,)}

    def walk(jaxpr, stack):
        for eqn in jaxpr.eqns:
            here = f"{stack}/{eqn.source_info.name_stack}"
            name = eqn.primitive.name
            if name in found and "rematted_computation" in here:
                found[name] += eqn.outvars[0].aval.shape == shapes[name]
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub, here)

    walk(jaxpr, "")
    return tuple(found.values())


@EXPERT_DECODERS
@pytest.mark.parametrize("n_keep", [0, 1, "all"])
def test_kept_expert_calls_replay_no_gather_and_no_grouped_product(
        n_keep, over):
    """A call that keeps ``MOE_RESIDUALS`` replays no gather of its
    sorted rows, neither the gate nor the up product and neither sort;
    a call that does not replays them all (under a held range: its
    forward loop, for window 0's three arrays).  What the backward
    loop of a held call rebuilds for a window PAST the first stands
    under a ``cond`` and no remat, kept or not."""
    model = _step_model(0, n_keep_moe=n_keep, **over)
    jaxpr = jax.make_jaxpr(model.train_step_fn)(*_step_args(model))
    replayed = model.remat_calls - model.remat_kept_moe_calls
    assert model.remat_calls == 2
    assert _replayed_expert_work(jaxpr.jaxpr, model) == (
        replayed, 2 * replayed, 2 * replayed)


@EXPERT_DECODERS
@pytest.mark.parametrize("tp", [1, 2])
def test_all_expert_calls_kept_is_bitwise_none_kept(tp, over):
    """Every expert call's rows and products kept against every
    call's rebuilt, alone and with the FFN width over a ``model`` axis
    (the mesh's ``expert`` axis is one device wide in both: a dropless
    layer refuses a wider one).  Compiled as written, as attention's
    comparison is."""
    _assert_bitwise(
        *(_loss_and_grads(
            _step_model(0, tp=tp, n_keep_moe=n_keep_moe, **over),
            {"xla_backend_optimization_level": 0})
          for n_keep_moe in ("all", 0)),
        leaves=["router", "we_gate", "we_up", "we_down"])


def _cell_model(cell):
    """The ``Llama`` of a benchmark cell, from its configuration's
    program block (nothing is placed: parameters materialise in
    ``compile_iter_fns``)."""
    from benchmark.drivers.train import program_config
    from benchmark.run import load_cell

    return Llama(program_config(load_cell(cell)["config"], seed=0,
                                n_replicas=1))


# ``peak_hbm_gib`` of the three transformer cells (ledger, PR 35)
LEDGER_PEAKS = {"mistral7b_train_t4096": 11.209,
                "olmoe_train_t4096": 12.668,
                "ouro_train_t4096": 12.97}


def test_estimate_knows_a_mamba_call_keeps_its_input_alone():
    """The hybrid cell read 14.04-14.12 GiB with all ten calls keeping
    their MLP products (my chip runs A and B, PR 47): the estimate
    (no product kept) and the kept bytes add up to it within the
    0.6 GiB the older cells' estimates lie within."""
    model = _cell_model("granite4h_micro_train_t8192")
    assert model.mixer_kinds.count("mamba") == 9
    kept = 10 * model.remat_kept_bytes_per_call
    assert abs((model.step_peak_estimate() + kept) / GIB - 14.1) < 0.7
    # parameters at 16 B, ten inputs, ONE attention call's flash
    # outputs (a mamba call keeps its input alone) and the head
    n_tok, isz = 8192, 2
    assert model.step_peak_estimate() == (
        16 * 772_160_448 + 10 * n_tok * 2048 * isz
        + n_tok * 32 * 64 * isz + 32 * 8192 * 4
        + 2 * n_tok * 12544 * isz)


@pytest.mark.parametrize("cell", sorted(LEDGER_PEAKS))
def test_estimate_reads_the_cells_peaks(cell):
    estimate = _cell_model(cell).step_peak_estimate() / GIB
    assert abs(estimate - LEDGER_PEAKS[cell]) < 0.6, estimate


@pytest.mark.parametrize("cell, limit_gib, n_mlp, n_attn, n_moe", [
    # at the chip's 15.75 GiB, the counts of the benchmark's seven
    # decoders (PERF.md §6, PRs 49 and 51)
    ("mistral7b_train_t4096", 15.75, 2, 2, 0),      # ample: all calls
    ("mellum2_train_t8192", 15.75, 0, 4, 4),        # expert layers alone
    ("olmoe_train_t4096", 15.75, 0, 1, 1),
    ("ouro_train_t4096", 15.75, 10, 1, 0),          # what 10 MLP calls leave
    # nine mamba calls and the attention call, every SwiGLU dense:
    # ten MLP calls leave 0.018 GB, attention's call takes 0.084
    ("granite4h_micro_train_t8192", 15.75, 10, 0, 0),
    # latent attention; the stack's four expert calls, not the MTP
    # block's
    ("glm47flash_train_t8192", 15.75, 1, 0, 4),
    # 0.052 GiB left by one MLP and five attention calls: one expert
    # call's 0.049 fits
    ("laguna_s21_train_t8192", 15.75, 1, 5, 1),
    ("mellum2_train_t8192", 14.25, 0, 4, 2),        # two of four fit
    ("mellum2_train_t8192", 13.0, 0, 3, 0),         # attention's first
    ("mistral7b_train_t4096", 12.0, 0, 0, 0),       # below the estimate
    ("mistral7b_train_t4096", None, 0, 0, 0),       # no device limit
    ("ouro_train_t4096", 64.0, 32, 32, 0),
    ("olmoe_train_t4096", 64.0, 0, 1, 1),           # an expert layer
    ("granite4h_micro_train_t8192", 64.0, 10, 1, 0),
    ("granite4h_micro_train_t8192", 13.5, 1, 0, 0),
], ids=str)
def test_keep_rule_from_shapes_and_the_limit(cell, limit_gib, n_mlp, n_attn,
                                             n_moe):
    model = _cell_model(cell)
    limit = None if limit_gib is None else int(limit_gib * GIB)
    assert model.remat_keep_calls(limit) == (n_mlp, n_attn, n_moe)
    if not limit:
        return
    # the MLP's copies first, attention's from what they leave, the
    # expert layer's from what both leave; where not every call of a
    # kind keeps its copies, the next call's would not have fitted
    room = max(
        limit - llama.REMAT_RESERVE_BYTES - model.step_peak_estimate(), 0)
    taken = []
    for kept, calls in (
        (n_mlp, [model.remat_kept_bytes_per_call]
         * model.ut_steps * model.layer_kinds.count("dense")),
        (n_attn, model._gqa_call_bytes),        # each at its layer's heads
        (n_moe, [model.remat_kept_moe_bytes_per_call]
         * model.ut_steps * model.layer_kinds.count("moe")),
    ):
        taken.append(sum(calls[len(calls) - kept:]))
        assert taken[-1] <= room
        if kept < len(calls):
            assert room - taken[-1] < calls[len(calls) - kept - 1]
        room -= taken[-1]
    (model.remat_kept_calls, model.remat_kept_attn_calls,
     model.remat_kept_moe_calls) = n_mlp, n_attn, n_moe
    assert model.remat_kept_bytes == sum(taken)


@pytest.mark.parametrize("cell, rows, row_bytes", [
    # every expert here: all 8 picks of 4 x 4096 tokens, a row of
    # 2048 and two products of 1024
    ("olmoe_train_t4096", 131072, (2048 + 2 * 1024) * 2),
    # held ranges: twice the balanced share of the picks
    ("mellum2_train_t8192", 65536, (2304 + 2 * 896) * 2),
    ("glm47flash_train_t8192", 16384, (2048 + 2 * 1536) * 2),
    ("laguna_s21_train_t8192", 5120, (3072 + 2 * 1024) * 2),
], ids=["all_here", "held_16_of_64", "held_8_of_64", "held_8_of_256"])
def test_expert_layer_weighs_its_rows_and_two_products(cell, rows, row_bytes):
    """What a dropless expert call keeps: its ``R`` sorted rows with
    their gate and up products in compute dtype and the sort's two
    ``int32[k N]``.  It names no MLP product; its attention block
    names what every grouped-query block names; no policy keeps the
    names of any of the three sets in EVERY call."""
    model = _cell_model(cell)
    picks = model.moe_top_k * model.config["batch_size"] * model.seq_len
    assert model.remat_kept_moe_bytes_per_call == (
        rows * row_bytes + 2 * picks * 4)
    assert not set(MLP_RESIDUALS + ATTN_RESIDUALS + MOE_RESIDUALS) & set(
        model.remat_saves)
    if cell == "olmoe_train_t4096":
        assert model.remat_kept_bytes_per_call == 0
        # q, k, v of 16 heads of 128 and a row of 2048, 4 x 4096 tokens
        assert model.remat_kept_attn_bytes_per_call == (
            4 * 4096 * (3 * 16 * 128 + 2048) * 2)


@pytest.mark.parametrize("cell, layers, per_token", [
    # latent attention's cheap residual is the latent, not the heads
    ("glm47flash_train_t8192", 0, 0),
    # a mamba call counts nothing: the one attention layer in ten
    # (32 heads and 8 key/value heads of 64, a row of 2048)
    ("granite4h_micro_train_t8192", 1, (32 + 2 * 8) * 64 + 2048),
    # k and v weigh what they weigh BEFORE the repeat (8 of 32 heads)
    ("mistral7b_train_t4096", 2, (32 + 2 * 8) * 128 + 4096),
], ids=["latent", "mamba", "gqa"])
def test_attention_names_weigh_what_the_block_keeps(cell, layers, per_token):
    model = _cell_model(cell)
    n_tok = model.config["batch_size"] * model.seq_len
    assert sum(model._gqa_layers) == layers
    assert model.remat_kept_attn_bytes_per_call == n_tok * per_token * 2


@pytest.mark.parametrize("over, keeps", [
    ({}, (2, 2, 0)), ({"remat": False}, (0, 0, 0)), ({"pp": 2}, (0, 0, 0)),
    # the capacity path names nothing of its expert layer
    ({"n_experts": 4, "moe_top_k": 2}, (0, 2, 0)),
    (EXPERTS["moe"], (0, 2, 2)), (EXPERTS["moe_held"], (0, 2, 2)),
    (dict(EXPERTS["moe"], remat=False), (0, 0, 0)),
    (dict(EXPERTS["moe"], first_k_dense=1), (1, 2, 1)),
    ({"tp": 2, "n_kv_heads": 2}, (2, 2, 0)),
], ids=["remat", "no_remat", "pipeline", "moe_capacity", "moe", "moe_held",
        "moe_no_remat", "moe_first_dense", "tp"])
def test_keep_rule_bypasses(over, keeps):
    model = Llama(dict(TINY, n_layers=2, **over))
    assert model.remat_keep_calls(64 * GIB) == keeps
    assert model.remat_keep_calls(None) == (0, 0, 0)


def test_compile_reads_the_devices_limit(monkeypatch):
    """What ``compile_iter_fns`` sets is the rule at the least limit
    the mesh's devices report."""
    seen = []

    def limit(devices):
        seen.extend(devices)
        return 64 * GIB

    monkeypatch.setattr(llama, "device_bytes_limit", limit)
    model = Llama(dict(TINY, n_layers=2))
    model.build_model(n_replicas=1)
    model.compile_iter_fns(mesh=make_mesh(data=1, devices=jax.devices()[:1]))
    assert (model.remat_kept_calls, model.remat_kept_attn_calls,
            model.remat_kept_moe_calls, seen) == (2, 2, 0, jax.devices()[:1])


@pytest.mark.parametrize("over", [{}, *EXPERTS.values()],
                         ids=["dense", *EXPERTS])
@pytest.mark.parametrize("limit_gib, kept", [(None, 0), (64, 1)])
def test_worker_summary_counts_the_kept_calls(monkeypatch, limit_gib, kept,
                                              over):
    from theanompi_tpu.workers import bsp_worker

    if limit_gib:
        monkeypatch.setattr(llama, "device_bytes_limit",
                            lambda devices: limit_gib * GIB)
    res = bsp_worker.run(
        devices=[0], modelfile="theanompi_tpu.models.llama",
        modelclass="Llama",
        config=dict(TINY, n_layers=1, n_epochs=1, seed=3, **over),
        verbose=False,
    )
    n_tok = TINY["batch_size"] * T
    # q, k, v (2 heads and 1 of 16) and the block's output: float32
    per_call = n_tok * (4 * 16 + TINY["dim"]) * 4
    if over:
        # the sorted rows laid out at a time with their gate and up
        # products, and the sort's two int32 arrays
        picks = over["moe_top_k"] * n_tok
        rows = moe.held_rows_bound(picks, over.get("moe_experts_held"),
                                   over["n_experts"])
        per_call += rows * (TINY["dim"] + 2 * FFN) * 4 + 2 * picks * 4
    else:
        per_call += 2 * n_tok * FFN * 4     # the gate and the up product
    assert (res["remat_calls"], res["remat_kept_calls"],
            res["remat_kept_attn_calls"], res["remat_kept_moe_calls"],
            res["remat_kept_bytes"]) == (
        1, 0 if over else kept, kept, kept if over else 0, kept * per_call)
    assert res["remat_saves"] == [
        *FLASH_RESIDUALS, *(["moe_tile_plan"] if over else [])]
