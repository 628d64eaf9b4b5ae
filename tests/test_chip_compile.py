"""The main path's kernels compile for the chip — without the chip.

The TPU compiler ships with jaxlib and compiles for a DESCRIBED
topology (``v5e:2x2``), so Mosaic's refusals — a slice not aligned to
the tiling, a 16-bit matmul accumulator, too much VMEM — show up here
at no chip time, where the Pallas interpreter accepts anything.  Every
shape is one the Llama proxy of ``chip_smoke.py`` runs:
training at T 2048 (hd 64, and the hd-128 GQA 4:1 variant), serving
with 8 slots, block 16, a 2048-token table, decode (Q=1) and a
speculative verify window (Q=4).

Nothing runs, so nothing here says anything about results or speed.
Skipped where the topology cannot be described.
"""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else logs under /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from theanompi_tpu.ops.attention import flash_attention_tpu
from theanompi_tpu.serving.paged_attention import paged_attend


@pytest.fixture(scope="module")
def chip():
    """Sharding on one described v5e chip, with the persistent
    compile cache off around the module: an entry written for a
    described device cannot be read back without one, and every later
    run would warn and recompile."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no libtpu / unknown topology name here
        pytest.skip(f"cannot describe a v5e topology: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compiled_text(fn, *shapes) -> str:
    return jax.jit(fn).lower(*shapes).compile().as_text()


# ([B, H, T, hd] after the GQA repeat — what Llama._layer hands the
# kernel —, window): the proxy (16 heads of 64) and its hd-128 variant
# (8 of 128); what the hybrid cell hands it, ONE attention layer's 32
# heads of 64 over 8192 positions (rows of half a register's lanes);
# the Mistral and OLMoE cells' call, GLM's, Mellum's window layers and
# Laguna's (72 heads under a window as wide as the row block)
FLASH_SHAPES = [
    ((4, 16, 2048, 64), None), ((4, 8, 2048, 128), None),
    ((1, 32, 8192, 64), None), ((2, 32, 4096, 128), None),
    ((2, 20, 8192, 256), None), ((2, 32, 8192, 128), 1024),
    ((1, 72, 8192, 128), 512),
]


@pytest.mark.parametrize("shape,window", FLASH_SHAPES, ids=str)
@pytest.mark.parametrize("direction", ["forward", "backward"])
def test_flash_attention_compiles_for_v5e(chip, shape, window, direction):
    """Mosaic takes the kernels at the cells' shapes — its VMEM and
    alignment refusals show here — and the backward compiles to ONE
    custom call that returns three arrays."""
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=chip)

    def forward(q, k, v):
        return flash_attention_tpu(q, k, v, causal=True, window=window)

    def backward(q, k, v):
        return jax.grad(
            lambda *a: forward(*a).astype(jnp.float32).sum(),
            argnums=(0, 1, 2),
        )(q, k, v)

    text = _compiled_text(forward if direction == "forward" else backward,
                          x, x, x)
    kernels = _flash_kernels(text.replace("_flash_window_jit", "_flash_jit"))
    assert kernels == (dict(fwd=1) if direction == "forward"
                       else dict(fwd=1, dkv=1))


def test_cells_flash_shape_compiles_to_the_two_kernels_the_reader_knows(chip):
    """The benchmark's two transformer cells hand the kernels
    ``[2, 32, 4096, 128]`` bf16 (OLMoE ``[4, 16, ...]``: the same 64
    batch-heads).  Forward and backward in one program compile to
    exactly two ``_flash_jit`` custom calls, and the benchmark's
    ``flash_attention_roofline`` reader tells them apart by what they
    return — ``(out, f32 logsumexp)`` and a tuple without a float32
    array, ``(dq, dk, dv)``, which it names ``dkv`` (and counts short:
    PERF.md §7) — whatever tiles the shape function chose."""
    from benchmark import hlo_read
    from benchmark.layer_metrics.flash_attention_roofline import kernel_kind

    x = jax.ShapeDtypeStruct((2, 32, 4096, 128), jnp.bfloat16, sharding=chip)

    def both(q, k, v):
        out, vjp = jax.vjp(
            lambda *a: flash_attention_tpu(*a, causal=True), q, k, v
        )
        return out, vjp(out)

    text = _compiled_text(both, x, x, x)
    kinds = sorted(
        kernel_kind(line)
        for line in hlo_read.custom_calls(text).values()
        if "_flash_jit" in line
    )
    assert kinds == ["dkv", "fwd"], kinds
    assert _flash_kernels(text) == dict(fwd=1, dkv=1)
    # the statistics cross the kernels' edge lane-dense: no
    # [B*H, T, 1] array, whose tiles are 128 times its values
    assert "f32[64,4096,1]" not in text and "f32[2,32,4096,1]" not in text


def test_glm_flash_shape_compiles_within_its_vmem_limit(chip):
    """GLM's latent attention hands the kernels ``[2, 20, 8192, 256]``
    bf16: the backward's float32 dQ sum is 8 MiB and its output block
    4 MiB twice, over Mosaic's default scope of 16 MiB with the
    operands beside them, so the call names its own limit
    (``_bwd_vmem_limit``) and compiles for the v5e within it: two
    custom calls, the backward's config holding that limit."""
    import re

    from theanompi_tpu.ops import attention

    x = jax.ShapeDtypeStruct((2, 20, 8192, 256), jnp.bfloat16, sharding=chip)

    def both(q, k, v):
        out, vjp = jax.vjp(
            lambda *a: flash_attention_tpu(*a, causal=True), q, k, v
        )
        return out, vjp(out)

    text = _compiled_text(both, x, x, x)
    assert _flash_kernels(text) == dict(fwd=1, dkv=1)
    limit = attention._bwd_vmem_limit(8192, 256, jnp.bfloat16)
    assert limit == (16 + 8 + 8) << 20
    backward = [
        line for line in text.splitlines()
        if "tpu_custom_call" in line and "_flash_jit" in line
        and "f32[" not in line.split("custom-call(")[0]
    ]
    assert len(backward) == 1
    scoped, used = (
        int(re.search(
            rf'"{key}":\[{{"memory_space":"1","offset":"0","size":"(\d+)"}}\]',
            backward[0]).group(1))
        for key in ("scoped_memory_configs", "used_scoped_memory_configs")
    )
    assert scoped == limit and 16 << 20 < used <= limit, (scoped, used)


@pytest.mark.parametrize("nq", [1, 4], ids=["decode", "verify4"])
@pytest.mark.parametrize(
    "hkv,rep,hd", [(8, 2, 64), (2, 4, 128)], ids=["hd64", "hd128"]
)
@pytest.mark.parametrize(
    "dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "fp32"]
)
def test_paged_attend_compiles_for_v5e(chip, dtype, hkv, rep, hd, nq):
    slots, bs, mb = 8, 16, 2048 // 16
    n_blocks = slots * mb

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=chip)

    pool = sds((n_blocks + 1, hkv, bs, hd), dtype)
    text = _compiled_text(
        paged_attend,                     # interpret=False: Mosaic
        sds((slots, nq, hkv, rep, hd), dtype), pool, pool,
        sds((slots, mb), jnp.int32), sds((slots, nq), jnp.int32),
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize(
    "direction", ["forward", "backward", "layer_remat", "layer_remat_kept"])
def test_dropless_expert_layer_compiles_to_grouped_kernels(
    chip, monkeypatch, direction
):
    """OLMoE's widths (2048 wide, 64 experts of 1024, 8 picks) at one
    sequence of 4096, the kernel path taken as on the chip: every
    grouped product of ``moe_ffn``'s dropless path is a Mosaic kernel
    of the repo's own (``ops/grouped_matmul.py``) whose work follows
    the rows — three forward, nine with the backward, eleven under the
    layer's remat and nine again under a remat that keeps
    ``MOE_RESIDUALS`` (the replay then holds neither the gate nor the
    up product, and no gather of the ``k N`` sorted rows) — found by
    ``ragged-dot`` in its line, as the
    benchmark's readers find it; none is XLA's rewrite of
    ``lax.ragged_dot`` (no ``ragged-dot-metadata`` table kernel); the
    tile plan is built once a layer call (the remat's replay holds
    none); and there is no ``[E, N, D]`` capacity buffer and no product
    over all 64 experts for every row (the 8x this path exists to
    avoid)."""
    import re

    from theanompi_tpu.ops import attention
    from theanompi_tpu.ops.grouped_matmul import TILE_PLAN_RESIDUAL
    from theanompi_tpu.parallel.moe import MOE_RESIDUALS, moe_ffn

    e, k, d, f, n = 64, 8, 2048, 1024, 4096
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)  # as on the chip

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    def forward(x, router, wg, wu, wd):
        y, aux = moe_ffn(
            x, router, wg, wu, wd, n_experts=e, top_k=k,
            capacity_factor=None, expert_axis=None, model_axis=None,
            renormalize=False,
        )
        return jnp.sum(y.astype(jnp.float32)) + aux["lb"] + aux["z"]

    fn = {
        "forward": forward,
        "backward": jax.value_and_grad(forward, argnums=(0, 1, 2, 3, 4)),
        "layer_remat": jax.value_and_grad(
            jax.checkpoint(
                forward,
                policy=jax.checkpoint_policies.save_only_these_names(
                    TILE_PLAN_RESIDUAL
                ),
            ),
            argnums=(0, 1, 2, 3, 4),
        ),
        "layer_remat_kept": jax.value_and_grad(
            jax.checkpoint(
                forward,
                policy=jax.checkpoint_policies.save_only_these_names(
                    TILE_PLAN_RESIDUAL, *MOE_RESIDUALS
                ),
            ),
            argnums=(0, 1, 2, 3, 4),
        ),
    }[direction]
    text = _compiled_text(
        fn, sds((1, n, d), jnp.bfloat16), sds((d, e), jnp.float32),
        sds((e, d, f), jnp.float32), sds((e, d, f), jnp.float32),
        sds((e, f, d), jnp.float32),
    )
    kernels = [line for line in text.splitlines()
               if "tpu_custom_call" in line and "ragged-dot" in line]
    products = [p for p in kernels
                if "ragged-dot-metadata" not in p.split("=", 1)[0]]
    assert len(products) == dict(forward=3, backward=9, layer_remat=11,
                                 layer_remat_kept=9)[direction]
    replayed = [ln for ln in text.splitlines()
                if "rematted_computation" in ln
                and ("ragged-dot-fwd" in ln.split("=", 1)[0]
                     or re.search(rf"= bf16\[{k * n},{d}\]\S* gather\(", ln)
                     or (" sort(" in ln and f"[{k * n}]" in ln))]
    assert bool(replayed) == (direction == "layer_remat"), replayed
    # the repo's own kernels, by name, and no table kernel of XLA's
    assert len(kernels) == len(products)
    assert all(re.match(r"\s*(ROOT )?%ragged-dot-(fwd|dlhs|drhs)\b", p)
               for p in products)
    # sorted rows [k*N, .] in, never a per-expert copy of the tokens
    assert all(re.search(rf"\[({k * n},({d}|{f})|{e},\d+,\d+)\]", p)
               for p in products)
    assert not re.search(rf"\[{e},{n},{d}\]|\[{e},{k * n},", text)
    # one tile plan a layer call: in ``moe_experts``, not in the replay
    plan = [line for line in text.splitlines() if "moe_tile_plan" in line]
    assert plan and all("moe_experts" in line for line in plan)
    assert not any("rematted_computation" in line for line in plan)


def test_latent_two_product_expert_layer_compiles_at_its_cells_shapes(
    chip, monkeypatch
):
    """Nemotron-3-Super's expert block as its cell runs it (4096 wide,
    a 1024 latent, 8 of 512 two-product experts of 2688 held, 22
    picks, 16384 tokens), with the backward: the grouped products are
    the repo's kernels at the bound's 11264 rows over ``[1024, 2688]``
    and ``[2688, 1024]`` blocks — no block whole (5.5 MB), the second
    contraction cut in three of 896 — none of them a gate product; the
    dispatch gathers rows of the LATENT's width, never the model's;
    the two projections stand under ``moe_latent``; and the window of
    23 MB keeps XLA's gathers for the tokens' sums (no ``held-rows-sum``
    kernel)."""
    import re

    from theanompi_tpu.ops import attention
    from theanompi_tpu.ops import grouped_matmul as gmm
    from theanompi_tpu.parallel.moe import held_rows_bound, moe_ffn

    e, held, k, d, lat, f, n = 512, 8, 22, 4096, 1024, 2688, 16384
    rows = held_rows_bound(k * n, held, e)
    assert rows == 11264
    assert gmm._rows_tiles(lat, f, jnp.bfloat16) == (1024, 896)
    assert gmm._rows_tiles(f, lat, jnp.bfloat16) == (896, 1024)
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)  # as on the chip

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    def loss(x, router, bias, wu, wd, down, up):
        y, _ = moe_ffn(
            x, router, None, wu, wd, n_experts=e, top_k=k,
            capacity_factor=None, expert_axis=None, model_axis=None,
            scoring="sigmoid", select_bias=bias, route_scale=5.0,
            held=held, latent=(down, up),
        )
        return jnp.sum(y.astype(jnp.float32))

    text = _compiled_text(
        jax.value_and_grad(loss, argnums=(0, 3, 4, 5, 6)),
        sds((2, n // 2, d), jnp.bfloat16), sds((d, e), jnp.float32),
        sds((e,), jnp.float32), sds((held, lat, f), jnp.float32),
        sds((held, f, lat), jnp.float32), sds((d, lat), jnp.float32),
        sds((lat, d), jnp.float32),
    )
    products = [line for line in text.splitlines()
                if "tpu_custom_call" in line and "ragged-dot" in line]
    assert products and all(
        re.match(r"\s*(ROOT )?%ragged-dot-(fwd|dlhs|drhs)\b", p)
        for p in products)
    assert {re.match(r"\s*(ROOT )?%ragged-dot-(\w+)", p).group(2)
            for p in products} == {"fwd", "dlhs", "drhs"}
    # the bound's rows at the latent's and the experts' widths, or a
    # held weight's gradient: never a row of the model's width
    assert all(re.search(
        rf"= (bf16\[{rows},({lat}|{f})\]|\w+\[{held},({lat},{f}|{f},{lat})\])",
        p) for p in products), products
    assert re.search(rf"= bf16\[{rows},{lat}\]\S* gather\(", text)
    assert not re.search(rf"\[{rows},{d}\]|\[{k * n},{d}\]", text)
    assert "held-rows-sum" not in text
    latent = [line for line in text.splitlines()
              if "moe_latent" in line and re.search(r" (dot|convolution)\(",
                                                     line)]
    assert latent and not any(
        scope in line for line in latent
        for scope in ("moe_experts", "moe_shared", "moe_dispatch"))


def test_held_layer_too_large_to_gather_from_sums_in_the_kernel(
    chip, monkeypatch
):
    """Mellum's expert layer as its cell runs it (2304 wide, 16 of 64
    experts of 896 held, 8 picks, 16384 tokens), with the backward:
    the window of 65536 sorted rows is a 302 MB source, so each
    token's rows are summed by ``ops/held_rows_sum.py``'s kernel —
    the combine, and the dispatch's transpose — over rows sorted by
    (expert, token), in the layer's loop and under its scopes, and no
    gather fetches a slot's 16384 rows.  The same layer at a quarter of the
    tokens (a 75 MB window) keeps XLA's gathers and has no such
    kernel."""
    import re

    from theanompi_tpu.ops import attention
    from theanompi_tpu.parallel.moe import moe_ffn

    e, held, k, d, f = 64, 16, 8, 2304, 896
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)  # as on the chip

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    def loss(x, router, wg, wu, wd):
        y, aux = moe_ffn(
            x, router, wg, wu, wd, n_experts=e, top_k=k,
            capacity_factor=None, expert_axis=None, model_axis=None,
            held=held,
        )
        return jnp.sum(y.astype(jnp.float32)) + aux["lb"]

    def text(n):
        return _compiled_text(
            jax.value_and_grad(loss, argnums=(0, 2, 3, 4)),
            sds((1, n, d), jnp.bfloat16), sds((d, e), jnp.float32),
            sds((held, d, f), jnp.float32), sds((held, d, f), jnp.float32),
            sds((held, f, d), jnp.float32),
        )

    def sums(text):
        return [ln for ln in text.splitlines()
                if "tpu_custom_call" in ln and "held-rows-sum" in ln]

    def slot_gathers(text, n):      # a slot's N rows out of the window
        return re.findall(rf"= bf16\[(?:1,)?{n},{d}\]\S* gather\(", text)

    big = text(16384)
    assert [re.search(r"moe_(combine|dispatch)/jit\(_sum_jit\)", ln).group(1)
            for ln in sums(big)] in (["combine", "dispatch"],
                                     ["dispatch", "combine"])
    assert all("while/body" in ln and f"f32[16384,{d}]" in ln
               for ln in sums(big))
    assert not slot_gathers(big, 16384)
    small = text(4096)
    assert not sums(small)
    assert slot_gathers(small, 4096)


def _fusions(text):
    """A compiled text as ``{computation: [(name, opcode, shape,
    operand names, called computation or None)]}`` and its fusion
    instructions as ``[(name, op_name, called computation)]``."""
    import re

    instr = re.compile(
        r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(\(.*?\)|\S+)\s+([\w\-]+)\((.*)$"
    )
    comps, calls, cur = {}, [], None
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->.*\{\s*$",
                        line)
        if head and not line.startswith(" "):
            cur = comps.setdefault(head.group(1), [])
            continue
        m = instr.match(line)
        if cur is None or not m:
            continue
        name, shape, opcode, rest = m.groups()
        called = re.search(r"calls=%?([\w.\-]+)", rest)
        cur.append((name, opcode, shape,
                    re.findall(r"%([\w.\-]+)", rest.split("),")[0]),
                    called and called.group(1)))
        op_name = re.search(r'op_name="([^"]*)"', rest)
        if opcode == "fusion" and called:
            calls.append((name, op_name.group(1) if op_name else "",
                          called.group(1)))
    return comps, calls


def _operand_side_opcodes(comps, comp):
    """For every ``convolution`` of a fused computation: its output
    shape and the opcodes it is fed from, nested fusions opened."""
    def opcodes(c):
        out = []
        for _, opcode, _, _, called in comps[c]:
            out += opcodes(called) if called else [opcode]
        return out

    by_name = {name: (opcode, operands, called)
               for name, opcode, _, operands, called in comps[comp]}
    found = []
    for name, opcode, shape, operands, called in comps[comp]:
        if called:
            found += _operand_side_opcodes(comps, called)
        if opcode != "convolution":
            continue
        seen, todo, fed = set(), list(operands), []
        while todo:
            n = todo.pop()
            if n in seen or n not in by_name:
                continue
            seen.add(n)
            op, ops, sub = by_name[n]
            fed += opcodes(sub) if sub else [op]
            todo += ops
        found.append((shape, fed))
    return found


def test_dense_mlp_backward_feeds_its_products_arrays(chip):
    """One dense ``Llama`` block under remat, backward with an SGD
    update, at small widths (256 wide, ffn 512, 2 x 256 tokens):
    SwiGLU's gradient is computed once, under the ``mlp_act_grad``
    scope, and no backward product reads an ``exponential`` through
    its OPERANDS except ``w_down``'s weight gradient (whose operand is
    the recomputed ``silu(g) * u``, left as it is: PERF.md, PR 27).

    These widths do reproduce XLA's choice: with the plain
    ``jax.nn.silu(g) * u`` in ``Llama._layer`` the same compile fuses
    the activation's gradient into the operand of both ``[256, 512]``
    weight gradients and both input-gradient products (4 fusions),
    which is what Mistral's ``[4096, 14336]`` ones showed on the chip
    (ledger, PR 26: 15.0 ms against 6.6 from shapes)."""
    import re

    from jax.sharding import NamedSharding, PartitionSpec as P

    from theanompi_tpu.models.llama import Llama
    from theanompi_tpu.ops import attention
    from theanompi_tpu.parallel import make_mesh

    dim, ffn, t, b = 256, 512, 256, 2
    mesh = make_mesh(data=1, devices=list(chip.device_set))
    rep = NamedSharding(mesh, P())
    model = Llama(dict(
        dim=dim, n_layers=1, n_heads=2, n_kv_heads=2, ffn_dim=ffn,
        vocab=256, seq_len=t, batch_size=b, compute_dtype="bfloat16",
    ))
    shapes = dict(
        attn_norm=(dim,), mlp_norm=(dim,), wq=(dim, dim), wk=(dim, dim),
        wv=(dim, dim), wo=(dim, dim), w_gate=(dim, ffn), w_up=(dim, ffn),
        w_down=(ffn, dim),
    )
    p = {k: jax.ShapeDtypeStruct(s, jnp.float32, sharding=rep)
         for k, s in shapes.items()}
    x = jax.ShapeDtypeStruct((b, t, dim), jnp.bfloat16, sharding=rep)
    layer = jax.checkpoint(model._layer)

    def sgd_step(p, x):
        def loss(p, x):
            return layer(p, x, jnp.arange(t)).astype(jnp.float32).sum()
        g, dx = jax.grad(loss, argnums=(0, 1))(p, x)
        return jax.tree.map(lambda a, b: a - 0.1 * b, p, g), dx

    on_tpu = attention._on_tpu
    attention._on_tpu = lambda: True       # the kernel, as on the chip
    try:
        text = _compiled_text(jax.shard_map(
            sgd_step, mesh=mesh, in_specs=(P(), P()), out_specs=P(),
        ), p, x)
    finally:
        attention._on_tpu = on_tpu
    assert "mlp_act_grad" in text
    comps, calls = _fusions(text)
    fed_an_exponential = [
        (name, shape)
        for name, op_name, comp in calls if "transpose(" in op_name
        for shape, fed in _operand_side_opcodes(comps, comp)
        if "exponential" in fed
    ]
    # w_down's weight gradient is [ffn, dim]; nothing else may be here
    assert all(re.match(rf"\w+\[{ffn},{dim}(,1)?\]", shape)
               for _, shape in fed_an_exponential), fed_an_exponential


def _flash_kernels(text):
    """How many flash kernels of each kind a compiled text holds: the
    ``tpu_custom_call``s whose ``op_name`` holds ``_flash_jit``, told
    apart by what they return through the benchmark's own
    ``kernel_kind``: ``(out, logsumexp)`` is the forward kernel, a
    tuple without a float32 array — ``(dq, dk, dv)`` — the backward
    one, under the reader's name for it, ``dkv`` (it counts the call
    as the ``(dk, dv)`` kernel it was written for, so short: PERF.md
    §7)."""
    import collections

    from benchmark.layer_metrics.flash_attention_roofline import kernel_kind

    return collections.Counter(
        kernel_kind(line) for line in text.splitlines()
        if "tpu_custom_call" in line and "_flash_jit" in line
    )


@pytest.mark.parametrize(
    "n_layers,moe", [(2, {}), (1, dict(n_experts=4, moe_top_k=2,
                                       capacity_factor=None))],
    ids=["dense_l2", "moe_l1"],
)
def test_layer_backward_replays_no_flash_forward_kernel(
    chip, monkeypatch, n_layers, moe
):
    """A ``Llama`` at small widths (256 wide, 2 heads of 128, 2 x 256
    tokens) through ``_forward``, a loss and ``jax.grad``: the
    compiled text holds two flash kernels a layer — forward and
    backward — because the layer's remat keeps ``FLASH_RESIDUALS``.  With
    the policy bypassed (full remat: the program before PR 29) the
    same function holds one more forward kernel for every layer whose
    replay XLA did not merge with its forward (it merges the LAST
    layer's when the loss follows it directly; here the final norm and
    the head lie between), so the count is taken against that compile,
    not against 4 x layers."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from theanompi_tpu.models.llama import Llama
    from theanompi_tpu.ops import attention
    from theanompi_tpu.parallel import make_mesh

    t, b = 256, 2
    mesh = make_mesh(data=1, devices=list(chip.device_set))
    batch = P("data", "seq")
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)  # as on the chip

    def kernels(bypass_policy):
        model = Llama(dict(
            dim=256, n_layers=n_layers, n_heads=2, n_kv_heads=2,
            ffn_dim=512, vocab=256, seq_len=t, batch_size=b,
            compute_dtype="bfloat16", **moe,
        ))
        if bypass_policy:
            model.remat_saves = ()

        def grad(params, ids):
            def loss(p):
                logits, aux, _ = model._forward(p, ids, with_aux=True)
                return logits.astype(jnp.float32).sum() + aux.sum()
            return jax.grad(loss)(params)

        params = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(
                a.shape, a.dtype, sharding=NamedSharding(mesh, P())
            ),
            jax.eval_shape(model._init_full_params, jax.random.key(0)),
        )
        ids = jax.ShapeDtypeStruct(
            (b, t), jnp.int32, sharding=NamedSharding(mesh, batch)
        )
        return _flash_kernels(_compiled_text(jax.shard_map(
            grad, mesh=mesh, in_specs=(P(), batch), out_specs=P(),
        ), params, ids))

    kept, full = kernels(False), kernels(True)
    assert kept == dict(fwd=n_layers, dkv=n_layers)
    assert 1 <= full["fwd"] - n_layers <= n_layers, full
    assert full["dkv"] == n_layers, full


def test_looped_decoder_step_compiles_with_its_scopes_and_one_exit_alive(
    chip, monkeypatch
):
    """A looped ``Llama`` (2 layers x 3 passes, sandwich norms, 256
    wide, 2 heads of 128, 2 x 256 tokens, vocabulary 4096) through
    ``_forward``, ``_exit_loss`` over the exits' dense head and
    ``jax.grad``, compiled for the v5e: two flash kernels a layer
    CALL (the layer's remat keeps ``FLASH_RESIDUALS`` in every pass),
    both scopes the benchmark's readers look for in the text, forward
    and backward, the exits' ``[N, V]`` logits never stacked over the
    passes (one exit's are alive at a time), and the head's three
    products all in the forward's one loop over the exits: none is
    replayed in the backward."""
    import re

    from jax.sharding import NamedSharding, PartitionSpec as P

    from theanompi_tpu.models.llama import Llama
    from theanompi_tpu.ops import attention
    from theanompi_tpu.parallel import make_mesh

    t, b, v, passes, layers = 256, 2, 4096, 3, 2
    mesh = make_mesh(data=1, devices=list(chip.device_set))
    batch = P("data", "seq")
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)  # as on the chip
    model = Llama(dict(
        dim=256, n_layers=layers, n_heads=2, n_kv_heads=2, ffn_dim=512,
        vocab=v, seq_len=t, batch_size=b, compute_dtype="bfloat16",
        ut_steps=passes, sandwich_norm=True, exit_beta=0.1,
        rope_theta=1e6, norm_eps=1e-6,
    ))

    def grad(params, ids, targets):
        def loss(p):
            exits = model._forward(p, ids, head=False)
            return model._exit_loss(
                p, exits.reshape(passes, -1, exits.shape[-1]),
                targets.reshape(-1))[0]
        return jax.grad(loss)(params)

    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=NamedSharding(mesh, P())
        ),
        jax.eval_shape(model._init_full_params, jax.random.key(0)),
    )
    ids = jax.ShapeDtypeStruct(
        (b, t), jnp.int32, sharding=NamedSharding(mesh, batch)
    )
    text = _compiled_text(jax.shard_map(
        grad, mesh=mesh, in_specs=(P(), batch, batch), out_specs=P(),
    ), params, ids, ids)
    calls = passes * layers
    assert _flash_kernels(text) == dict(fwd=calls, dkv=calls)
    for scope in ("jvp(ut_stack)", "transpose(jvp(ut_stack))",
                  "jvp(ut_exit)", "transpose(jvp(ut_exit))"):
        assert scope in text, scope
    n = b * t
    assert re.search(rf"\[{n},{v}\]", text)
    assert not re.search(rf"\[{passes},{n},{v}\]", text)
    head = [ln for ln in text.splitlines()
            if " convolution(" in ln and "ut_exit" in ln]
    assert len(head) == 3, head
    assert not any("transpose(jvp(ut_exit))" in ln for ln in head), head


# -- the step program's blocks (benchmark/layer_metrics/_blocks.py) ----------

_KERNELS = {"flash_attention": {"hlo_part": "_flash_jit"},
            "moe_grouped_matmul": {"hlo_part": "ragged-dot"}}
# what does work at the top level of a compiled step: the rest
# (constants, parameters, bitcasts, tuples) takes no device time
_WORK = (" fusion(", " custom-call(", " convolution(", " copy(", " reduce(",
         " reduce-window(", " scatter(", " gather(", " sort(",
         " select-and-scatter(", " dynamic-update-slice(", " dynamic-slice(")


def _step_blocks(text):
    """``({(block, phase)}, {instruction: entry} of the top-level
    instructions without a block that do work, the entries of the
    Pallas kernels and of the fusions that hold a product)``."""
    from benchmark.layer_metrics import _blocks

    entries = _blocks.instruction_blocks(
        {"hlo_text": text, "cell": {"config": {"kernels": _KERNELS}}})
    comps = _blocks.computations(text)
    fused = set()
    top, kernels, products = {}, {}, {}
    for lines in comps.values():
        for line in lines:
            called = _blocks._CALLS.search(line)
            if called and " fusion(" in line:
                fused.add(called.group(1))
    for comp, lines in comps.items():
        if comp in fused:
            continue
        for line in lines:
            name = _blocks.hlo_read._INSTR.match(line).group(1)
            entry = entries[name]
            if "tpu_custom_call" in line:
                kernels[name] = entry
            called = _blocks._CALLS.search(line)
            if called and any(_blocks._PRODUCT.search(ln)
                              for ln in comps.get(called.group(1), ())):
                products[name] = entry
            if entry["block"] == "other" and any(w in line for w in _WORK):
                top[name] = entry
    have = {(e["block"], e["phase"]) for e in entries.values()}
    return have, top, kernels, products


def _llama_step_text(chip, monkeypatch, n_keep=0, lowered=False,
                     n_keep_attn=0, n_keep_moe=0, **knobs):
    """The compiled text (``lowered``: the lowered text, which names
    no source line outside the kernels' bodies) of a small ``Llama``'s
    real train step
    (``compile_iter_fns``: ``value_and_grad`` of ``loss_fn``, then
    ``ExchangePlan.apply``) for the v5e; the parameters are shapes, so
    nothing is placed.  ``n_keep``, ``n_keep_attn`` and ``n_keep_moe``
    stand in for the device's memory (a described device reports none:
    0 calls keep the MLP's products, 0 attention's, 0 the expert
    layer's)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from theanompi_tpu.models.llama import Llama
    from theanompi_tpu.ops import attention
    from theanompi_tpu.parallel import make_mesh

    t, b = 256, 2
    mesh = make_mesh(data=1, devices=list(chip.device_set))
    rep = NamedSharding(mesh, P())
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)  # as on the chip
    monkeypatch.setattr(Llama, "remat_keep_calls",
                        lambda self, limit: (n_keep, n_keep_attn, n_keep_moe))
    model = Llama(dict(dict(
        dim=256, n_layers=2, n_heads=2, n_kv_heads=2, ffn_dim=512,
        vocab=4096, seq_len=t, batch_size=b, compute_dtype="bfloat16",
        remat=True, optimizer="adam", xent_chunks=1,
    ), **knobs))
    model.build_model(n_replicas=1)

    def shapes(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=rep),
            tree)

    model.params = shapes(
        jax.eval_shape(model._init_full_params, jax.random.key(0)))
    model.opt_state = shapes(
        jax.eval_shape(model.optimizer.init, model.params))
    if model.moe_select_bias:
        model.net_state = shapes(jax.eval_shape(model._init_net_state))
    model.compile_iter_fns(mesh=mesh)
    ids = jax.ShapeDtypeStruct(
        (b, t), jnp.int32, sharding=NamedSharding(mesh, P("data", "seq")))
    lr = jax.ShapeDtypeStruct((), jnp.float32, sharding=rep)
    step = model._train_step.lower(
        model.params, model.opt_state, model.ef_state, ids, ids, lr,
        *model._state_args(),
    )
    return step.as_text() if lowered else step.compile().as_text()


# an ``op_name`` outside every layer and every block: the step's own
# glue (the MoE aux moments of ``_forward``, the loss's aux terms, a
# classifier's cast of its input)
_GLUE = r"jit\(\w+\)/(jvp\(\)/|transpose\(jvp\(\)\)/)?[\w\-]+"


@pytest.mark.parametrize("knobs, kernel_blocks, most_unnamed", [
    (dict(), {"blk_attn"}, 20),
    (dict(n_experts=8, moe_top_k=2, ffn_dim=256, capacity_factor=None),
     {"blk_attn", "blk_ffn"}, 70),
], ids=["dense", "dropless_moe"])
def test_llama_step_names_its_blocks_in_every_phase(
    chip, monkeypatch, knobs, kernel_blocks, most_unnamed
):
    """A plain ``Llama`` (2 layers under remat, the dense head, Adam)
    and a dropless MoE one through the model's own train step,
    compiled for the v5e: every block in each phase it has (the head
    and the embedding are outside the remat: no replay), every flash
    and grouped-product kernel and every fusion that holds a product
    under a block, the optimizer's instructions under ``opt_update``,
    and what is left without a block either XLA's own (no ``op_name``:
    copies, slices, ``ConcatBitcast``) or the step's glue outside
    every layer."""
    import re

    have, top, kernels, products = _step_blocks(
        _llama_step_text(chip, monkeypatch, **knobs))
    expected = {
        (block, phase)
        for block in ("blk_attn", "blk_ffn")
        for phase in ("fwd", "replay", "bwd")
    } | {("blk_embed", "fwd"), ("blk_embed", "bwd"), ("blk_head", "fwd"),
         ("blk_head", "bwd"), ("opt_update", "fwd")}
    assert expected <= have, expected - have
    assert not {p for b, p in have if b in ("blk_head", "blk_embed")} & {"replay"}
    assert kernels and {e["block"] for e in kernels.values()} == kernel_blocks
    assert {e["phase"] for e in kernels.values()} >= {"fwd", "bwd"}
    assert products and "other" not in {
        e["block"] for e in products.values()}, products
    # a weight gradient fused with its Adam update: once under its
    # block, flagged for ``opt_update_ms``
    assert any(e["carries_opt"] and e["block"].startswith("blk_")
               for e in products.values())
    named = [e["op_name"] for e in top.values() if e["op_name"]]
    assert all(re.fullmatch(_GLUE, n) for n in named), named
    assert len(top) <= most_unnamed, sorted(top)


# a hybrid stack at small widths: two mamba layers around an attention
# layer at head dim 64 without rotation, the four multipliers, a tied
# head; two chunks of the scan
_HYBRID = dict(
    n_layers=3, n_heads=4, n_kv_heads=2,
    layer_types=["mamba", "attention", "mamba"], mamba_n_heads=8,
    mamba_d_head=64, mamba_d_state=128, mamba_n_groups=1, mamba_d_conv=4,
    mamba_expand=2, mamba_chunk_size=128, embedding_multiplier=12,
    residual_multiplier=0.22, attention_multiplier=0.015625,
    logits_scaling=8, tie_word_embeddings=True,
    position_embedding_type="nope",
)


def test_hybrid_step_compiles_with_the_mixers_scopes_in_every_phase(
    chip, monkeypatch
):
    """A stack with mamba layers through the model's own train step,
    compiled for the v5e: the mixer's block in all three phases beside
    the attention layer's, its four scopes in the text, the ONE
    attention layer's two flash kernels at head dim 64, the scan's
    two named kernels under ``blk_ssm/ssd_scan`` in forward, replay
    and backward with no ``[.., chunk, chunk]``-a-head array left
    beside them, every fusion that holds a product under a block, and
    no rotation anywhere."""
    import re
    from collections import Counter

    from benchmark import hlo_read

    text = _llama_step_text(chip, monkeypatch, **_HYBRID)
    have, top, kernels, products = _step_blocks(text)
    expected = {
        (block, phase)
        for block in ("blk_ssm", "blk_attn", "blk_ffn")
        for phase in ("fwd", "replay", "bwd")
    } | {("blk_embed", "fwd"), ("blk_embed", "bwd"), ("blk_head", "fwd"),
         ("blk_head", "bwd"), ("opt_update", "fwd")}
    assert expected <= have, expected - have
    for scope in ("ssm_proj", "ssm_conv", "ssd_scan", "ssm_gate_norm"):
        assert f"blk_ssm/{scope}/" in text or f"blk_ssm)/{scope}/" in text
    calls = hlo_read.custom_calls(text)
    assert len([ln for ln in calls.values() if "_flash_jit" in ln]) == 2
    assert {e["block"] for e in kernels.values()} == {"blk_attn", "blk_ssm"}
    # the scan: one forward kernel a mamba layer in the forward and one
    # in the replay, one backward kernel, each under the mixer's block
    # AND the scan's scope (the backward rule inherits its call's)
    scans = Counter(
        (name.rsplit(".", 1)[0], e["phase"])
        for name, e in kernels.items() if e["block"] == "blk_ssm"
        and re.search(r"blk_ssm\)?/ssd_scan/", e["op_name"]))
    assert scans == {("ssd-chunk-fwd", "fwd"): 2, ("ssd-chunk-fwd", "replay"): 2,
                     ("ssd-chunk-bwd", "bwd"): 2}, scans
    # the decay mask, the masked scores and their cotangents are
    # ``[B, (G,) H, chunks, chunk, chunk]``: none is an array of the
    # step (the parent wrote 98 such under the scope, float32 and bf16)
    chunk = _HYBRID["mamba_chunk_size"]
    masks = [
        ln for ln in text.splitlines() if "ssd_scan" in ln
        and re.search(rf"\[(\d+,){{2,}}{chunk},{chunk}\]", ln)]
    assert not masks, masks[:3]
    assert products and "other" not in {
        e["block"] for e in products.values()}, products
    assert " cosine(" not in text and " sine(" not in text
    # the tied matrix: one leaf, so one Adam update of [vocab, dim]
    assert "lm_head" not in text


@pytest.mark.parametrize("direction", ["forward", "backward"])
def test_scan_kernels_compile_for_v5e_at_the_cells_widths(chip, direction):
    """``ssd-chunk-fwd`` / ``ssd-chunk-bwd`` at the hybrid cell's
    published shapes (1 x 8192 tokens, 64 heads of 64 over a state of
    128 in one group, chunks of 256): Mosaic takes the lane slices,
    the transposes and the VMEM the kernels ask for."""
    from theanompi_tpu.ops import ssd, ssd_kernel

    b, t, h, p, g, n, chunk = 1, 8192, 64, 64, 1, 128, 256
    tiles = ssd_kernel.scan_tiles(t, chunk, p, n, h // g, h)
    assert tiles == ssd_kernel.ScanTiles(256, 16, 128)

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=chip)

    args = (shape((b, t, h, p), jnp.bfloat16), shape((b, t, h), jnp.float32),
            shape((h,), jnp.float32), shape((b, t, g, n), jnp.bfloat16),
            shape((b, t, g, n), jnp.bfloat16), shape((h,), jnp.float32))

    def forward(*a):
        return ssd._scan_on_kernels(*a, tiles, with_stats=True)

    def backward(*a):
        return jax.grad(
            lambda *a: forward(*a)[0].astype(jnp.float32).sum(),
            argnums=range(6))(*a)

    text = _compiled_text(forward if direction == "forward" else backward,
                          *args)
    names = {"forward": ["ssd-chunk-fwd"],
             "backward": ["ssd-chunk-fwd", "ssd-chunk-bwd"]}[direction]
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert len(calls) == len(names)
    for name in names:
        assert any(name in ln for ln in calls), name
    # nothing of a head's [chunk, chunk] in HBM
    assert f"{chunk},{chunk}]" not in text


# a decoder with every mechanism of the ``glm4_moe_lite`` cell at small
# widths: latent attention whose kernels see a head dim of 256, a
# leading dense layer, expert layers with 2 of 8 experts held under a
# sigmoid router with a selection bias, a shared expert, an MTP module
_MLA_MOE = dict(
    n_layers=2, attention="mla", q_lora_rank=128, kv_lora_rank=128,
    qk_nope_head_dim=192, qk_rope_head_dim=64, v_head_dim=256,
    n_experts=8, moe_top_k=2, ffn_dim=256, capacity_factor=None,
    moe_scoring="sigmoid", moe_route_scale=1.8, moe_bias_rate=0.001,
    moe_experts_held=2, moe_shared_experts=1, first_k_dense=1,
    dense_ffn_dim=512, mtp_depth=1, moe_aux_coef=0.0,
)


@pytest.mark.parametrize("n_keep_moe", [0, 1])
def test_held_share_step_compiles_with_its_kernels_and_scopes(
    chip, monkeypatch, n_keep_moe
):
    """The cell's kind of step (``_MLA_MOE``: a dense call, an expert
    call and the MTP module's expert call) compiled for the v5e: two
    flash kernels a layer CALL at head dim 256 (the remat keeps the
    kernel's outputs in the MTP block too); the grouped kernels of the
    two expert calls against leaves of the 2 experts held, over the
    static bound of 512 of the ``k * N`` = 1024 sorted rows (the held
    range shortens the visits at run time, not the grid) — three
    forward and six backward a call, and the gate and the up product
    twice more: in the replay of a call whose remat does not keep
    ``MOE_RESIDUALS`` (its forward loop again, for window 0's rows and
    products; the down product dropped) and NOT in the replay of the
    ``n_keep_moe`` stack calls whose remat does (the MTP block's keeps
    the plain policy), and under the backward loop's ``cond`` for a
    window past the first, kept or not; all of them in the loops over
    the windows of 512 rows, whose first pass every routing runs and
    whose second only a routing past the bound; none over
    1024 rows anywhere, and no second set beside the loops; window 0's
    tile plan made outside the loop, once a call and not in a replay;
    the new scopes in every phase they have; every block named; and
    the step gives the selection bias back."""
    import re
    from collections import Counter

    from benchmark.layer_metrics import _scopes

    text = _llama_step_text(chip, monkeypatch, n_keep_moe=n_keep_moe,
                            **_MLA_MOE)
    assert _flash_kernels(text) == dict(fwd=3, dkv=3)
    flash = [ln for ln in text.splitlines()
             if "tpu_custom_call" in ln and "_flash_jit" in ln]
    assert all(re.search(r"bf16\[\d+,256,256\]", ln) for ln in flash), flash
    products = [ln for ln in text.splitlines()
                if "tpu_custom_call" in ln and "ragged-dot" in ln]

    def phase(ln):
        return ("replay" if "rematted_computation" in ln
                else "bwd" if "transpose(" in ln else "fwd")

    def in_loop(ln):                # the step itself is no loop here
        return "while/body/" in ln

    assert all(map(in_loop, products))
    assert Counter(map(phase, products)) == dict(
        fwd=2 * 3, replay=(2 - n_keep_moe) * 2, bwd=2 * (6 + 2))
    rebuilt = [ln for ln in products if "/cond/" in ln]
    assert len(rebuilt) == 2 * 2 and all(
        "ragged-dot-fwd" in ln and phase(ln) == "bwd" for ln in rebuilt)
    rows = 2 * 2 * 256              # k * N; the bound is half of them
    assert all(re.search(rf"\[{rows // 2},256\]", ln) for ln in products)
    assert not any(re.search(rf"\[{rows},256\]", ln) for ln in products)
    assert not re.search(r"\[8,256,256\]", text)    # no leaf of all 8
    plan = [ln for ln in text.splitlines()
            if "moe_tile_plan" in ln and not in_loop(ln)]
    assert plan and not any("rematted_computation" in ln for ln in plan)
    have, top, kernels, products = _step_blocks(text)
    for block in ("blk_attn", "blk_ffn"):
        assert {(block, ph) for ph in ("fwd", "replay", "bwd")} <= have
    assert {("blk_mtp_in", "fwd"), ("blk_mtp_in", "bwd")} <= have
    assert "other" not in {e["block"] for e in products.values()}
    for scope, phases in (("mla_proj", 3), ("moe_shared", 3), ("mtp", 3)):
        names = _scopes._under(text, scope)
        lines = [ln for ln in text.splitlines()
                 if (m := re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = ", ln))
                 and m.group(1) in names]
        seen = {"replay" if "rematted_computation" in ln
                else "bwd" if "transpose(" in ln else "fwd" for ln in lines}
        assert len(seen) == phases, (scope, seen)
    # the MTP module's kernels lie under its scope
    assert sum("jvp(mtp)" in ln for ln in flash) == 2


def _route_phases(text, primitive, opcode=None):
    """``{phase: n}`` of the instructions of a compiled step (those of
    ``opcode``, if given) whose ``op_name`` lies under ``moe_route``
    and names ``primitive`` after it."""
    import re
    from collections import Counter

    return Counter(
        "replay" if "rematted_computation" in ln
        else "bwd" if "transpose(" in ln else "fwd"
        for ln in text.splitlines()
        if (opcode is None or f" {opcode}(" in ln) and re.search(
            rf'op_name="[^"]*moe_route/[^"]*\b{primitive}\b', ln))


@pytest.mark.parametrize("n_keep_moe", [0, 1])
def test_sigmoid_router_step_gathers_no_picked_score(
    chip, monkeypatch, n_keep_moe
):
    """The held-share step's two expert calls (a stack layer's and the
    MTP module's) under a sigmoid router with a selection bias: the
    picked scores leave the pass that picks them (PERF.md, PR 56), so
    no ``gather`` stands under ``moe_route`` in the forward pass, the
    replay or the backward pass, and its sorts there are ONE a router
    call, forward and replay (a call that keeps ``MOE_RESIDUALS`` still
    replays its router), none in the backward pass: a held share's
    gates carry no gradient.  The plain form in the program's place
    shows what the assertion looks for."""
    from theanompi_tpu.parallel import moe

    text = _llama_step_text(chip, monkeypatch, n_keep_moe=n_keep_moe,
                            **_MLA_MOE)
    assert not _route_phases(text, "gather")
    assert _route_phases(text, "sort", "sort") == dict(fwd=2, replay=2)
    if n_keep_moe:
        return

    def plain(scores, chosen, top_k):
        _, eidx = jax.lax.top_k(chosen, top_k)
        return jnp.take_along_axis(scores, eidx, axis=-1), eidx

    monkeypatch.setattr(moe, "_picked_scores", plain)
    text = _llama_step_text(chip, monkeypatch, **_MLA_MOE)
    assert set(_route_phases(text, "gather")) == {"fwd", "replay"}


@pytest.mark.parametrize("n, e, k", [(16384, 512, 22), (16384, 64, 4)],
                         ids=["nemotron", "glm"])
def test_sigmoid_router_compiles_to_one_sort_at_the_cells_shapes(chip, n, e, k):
    """``router_topk``'s sigmoid branch at the two cells' shapes, for
    the v5e: ONE sort of three operands along the experts (the key,
    the scores, the indices) and two ``[N, k]`` slices of its results;
    no gather, no packing of (row, column) pairs for one, and no
    ``[N, k, E]`` array."""
    import re

    from theanompi_tpu.parallel.moe import router_topk

    def sds(shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=chip)

    text = _compiled_text(
        lambda x2, w, b: router_topk(x2, w, k, True, scoring="sigmoid",
                                     select_bias=b, scale=2.5)[:2],
        sds((n, 128)), sds((128, e)), sds((e,)))
    sorts = [ln for ln in text.splitlines() if " sort(" in ln]
    assert len(sorts) == 1
    assert re.search(rf"= \(s32\[{n},{e}\]\S*, f32\[{n},{e}\]\S*, "
                     rf"s32\[{n},{e}\]\S*\) sort\(", sorts[0]), sorts[0]
    assert " gather(" not in text and "GatherScatterIndices" not in text
    assert f"[{n},{k},{e}]" not in text


def test_latent_attention_hands_the_kernels_products_not_joins(
    chip, monkeypatch
):
    """The same step: latent attention's projections cut and join the
    WEIGHT ``wkv_b``, not the activations (PR 38).  No array of
    ``qk_nope_head_dim + v_head_dim`` = 448 a head exists but the
    weight-shaped ones (``wkv_b`` itself, its gradient and its Adam
    state, ``[128, 2 * 448(, 1)]`` or ``[128, 2, 448]``): ``wkv_b``'s
    product is never written whole and cut at lane 192, and its
    gradient never re-assembled.  The two flash kernels a block
    take the operands they took — q, k, v (and the gradients)
    ``[B * H, T, 256]`` — and ``mla_proj``'s instructions, the
    products among them, show in the forward, the replay and the
    backward."""
    import re

    from benchmark.layer_metrics import _scopes

    text = _llama_step_text(chip, monkeypatch, **_MLA_MOE)
    rank, heads = _MLA_MOE["kv_lora_rank"], 2
    width = _MLA_MOE["qk_nope_head_dim"] + _MLA_MOE["v_head_dim"]
    joined = {
        dims for dims in re.findall(r"\b(?:bf16|f32)\[([\d,]+)\]", text)
        if {str(width), str(heads * width)} & set(dims.split(","))
    }
    assert joined and joined <= {
        f"{rank},{heads * width}", f"{rank},{heads * width},1",
        f"{rank},{heads},{width}"}, joined
    assert _flash_kernels(text) == dict(fwd=3, dkv=3)
    operand = rf"bf16\[{2 * heads},256,256\]"       # [B * H, T, hd]
    for ln in text.splitlines():
        if "tpu_custom_call" in ln and "_flash_jit" in ln:
            took = re.search(
                r"operand_layout_constraints=\{((?:[^{}]|\{[^{}]*\})*)\}", ln
            ).group(1)
            # fwd: q k v; dK/dV and dQ: q k v and the output's
            # gradient; the float32 statistics beside them
            assert len(re.findall(operand, took)) in (3, 4), took
            assert set(re.findall(r"bf16\[[\d,]+\]", took)) == {
                f"bf16[{2 * heads},256,256]"}, took
    names = _scopes._under(text, "mla_proj")
    products = _step_blocks(text)[3]
    phases, product_phases = set(), set()
    for ln in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = ", ln)
        if m and m.group(1) in names:
            phase = ("replay" if "rematted_computation" in ln
                     else "bwd" if "transpose(" in ln else "fwd")
            phases.add(phase)
            if m.group(1) in products:
                product_phases.add(products[m.group(1)]["phase"])
    assert phases == product_phases == {"fwd", "replay", "bwd"}


def test_grouped_query_attention_hands_the_kernels_products_not_relays(
    chip, monkeypatch
):
    """A small GQA 2:1 decoder's step (PR 45): between ``attn_norm``
    and the flash kernels no activation is re-laid or lane-sliced.
    Under ``gqa_proj`` — the three products, the two rotations, the
    repeat — stand instructions of the forward, the replay and the
    backward, products among them in each, and none of them is a
    ``copy`` or a ``transpose`` of a ``[B, H, T, hd]`` activation
    (what is copied there is a weight's bf16 cast or a ``[T, hd]``
    rotary table); the whole text holds no stride-2 ``slice`` and no
    array of a row's halves; two flash kernels a layer take what
    they took."""
    import re

    from benchmark.layer_metrics import _scopes

    heads, kv_heads, hd, b, t = 4, 2, 128, 2, 256
    text = _llama_step_text(chip, monkeypatch, dim=512, n_heads=heads,
                            n_kv_heads=kv_heads)
    assert _flash_kernels(text) == dict(fwd=2, dkv=2)
    # ``rope``'s old ``x[..., 0::2]`` / ``x[..., 1::2]``: a stride-2
    # slice, which XLA:TPU runs over arrays of the pairs' halves
    assert not re.findall(r"slice=\{[^}]*\[\d+:\d+:2\][^}]*\}", text)
    halves = re.findall(rf"\b(?:bf16|f32)\[{b},\d+,{t},{hd // 2}\b[^\]]*\]", text)
    assert not halves, sorted(set(halves))
    names = _scopes._under(text, "gqa_proj")
    products = _step_blocks(text)[3]
    activations = {tuple(sorted((b, h, t, hd))) for h in (heads, kv_heads)}
    phases, product_phases, relays = set(), set(), []
    for ln in text.splitlines():
        m = re.match(
            r"\s*(?:ROOT )?%?([\w.\-]+) = \w+\[([\d,]*)\]\S* ([\w\-]+)\(", ln)
        if not (m and m.group(1) in names):
            continue
        phases.add("replay" if "rematted_computation" in ln
                   else "bwd" if "transpose(" in ln else "fwd")
        if m.group(1) in products:
            product_phases.add(products[m.group(1)]["phase"])
        dims = tuple(sorted(int(d) for d in m.group(2).split(",") if d))
        if m.group(3) in ("copy", "transpose") and dims in activations:
            relays.append(ln.strip()[:160])
    assert phases == product_phases == {"fwd", "replay", "bwd"}
    assert not relays, relays


# -- window and full attention layers mixed (PR 41) ---------------------------


def _window_kernels(text):
    """``_flash_kernels`` for the window calls: the custom calls under
    ``_flash_window_jit``, by what they return."""
    return _flash_kernels("\n".join(
        ln.replace("_flash_window_jit", "_flash_jit")
        for ln in text.splitlines() if "_flash_window_jit" in ln
    ))


def test_cells_window_shape_compiles_to_two_kernels_of_their_own_name(chip):
    """The ``mellum`` cell's window layers hand the kernels ``[2, 32,
    8192, 128]`` bf16 under a window of 1024.  Forward and backward in
    one program compile for the v5e to exactly two custom calls
    under ``_flash_window_jit`` — and none whose line holds
    ``_flash_jit``, the name ``flash_attention_roofline`` holds every
    call it matches to the causal triangle's count by — told apart by
    what they return as the full calls are."""
    from benchmark import hlo_read
    from benchmark.layer_metrics.flash_attention_roofline import kernel_kind

    x = jax.ShapeDtypeStruct((2, 32, 8192, 128), jnp.bfloat16, sharding=chip)

    def both(q, k, v):
        out, vjp = jax.vjp(
            lambda *a: flash_attention_tpu(*a, causal=True, window=1024),
            q, k, v,
        )
        return out, vjp(out)

    text = _compiled_text(both, x, x, x)
    calls = hlo_read.custom_calls(text)
    assert len(calls) == 2
    assert not any("_flash_jit" in line for line in calls.values())
    kinds = sorted(
        kernel_kind(line) for line in calls.values()
        if "_flash_window_jit" in line
    )
    assert kinds == ["dkv", "fwd"], kinds
    assert _window_kernels(text) == dict(fwd=1, dkv=1)
    assert "f32[64,8192,1]" not in text


# two layers, one of each kind, heads of 128 over a width of 256 (4 x
# 128 = 512: the projections are not square), a window under T, a
# rotary table a kind, 2 of 8 softmax-routed experts held
_WINDOW_MOE = dict(
    n_layers=2, n_heads=4, n_kv_heads=2, head_dim=128,
    layer_types=["sliding_attention", "full_attention", "sliding_attention"],
    sliding_window=128,
    rope_parameters={
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
            "original_max_position_embeddings": 64, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default", "rope_theta": 500000},
    },
    n_experts=8, moe_top_k=2, ffn_dim=256, capacity_factor=None,
    moe_experts_held=2, moe_aux_coef=0.001,
)


def test_mixed_attention_step_compiles_with_each_kinds_kernels_and_scopes(
    chip, monkeypatch
):
    """The cell's kind of step compiled for the v5e: the full layer's
    two flash kernels under ``_flash_jit`` and the window layer's
    two under ``_flash_window_jit`` (no third: the remat keeps both
    kinds' forward outputs), each against operands of the PUBLISHED
    head dim; every kernel, window or full, forward and backward,
    carries ``blk_attn`` and its kind's scope in its own ``op_name``
    (the benchmark's block join knows no ``window_attention`` kernel
    and reads the block there); ``attn_sliding`` and ``attn_full``
    hold instructions in the forward, the replay and the backward;
    every block named."""
    import re

    from benchmark.layer_metrics import _scopes

    text = _llama_step_text(chip, monkeypatch, **_WINDOW_MOE)
    assert _flash_kernels(text) == dict(fwd=1, dkv=1)
    assert _window_kernels(text) == dict(fwd=1, dkv=1)
    flash = [ln for ln in text.splitlines()
             if "tpu_custom_call" in ln and "_flash" in ln]
    assert len(flash) == 4
    assert all(re.search(r"bf16\[8,256,128\]", ln) for ln in flash), flash
    for ln in flash:
        op_name = re.search(r'op_name="([^"]*)"', ln).group(1)
        scope = ("attn_sliding" if "_flash_window_jit" in ln
                 else "attn_full")
        assert "blk_attn" in op_name and scope in op_name, op_name
        assert ("_flash_jit" in ln) != ("_flash_window_jit" in ln)
    have, top, kernels, products = _step_blocks(text)
    for block in ("blk_attn", "blk_ffn"):
        assert {(block, ph) for ph in ("fwd", "replay", "bwd")} <= have
    assert "other" not in {e["block"] for e in kernels.values()}
    assert "other" not in {e["block"] for e in products.values()}
    for scope in ("attn_sliding", "attn_full"):
        names = _scopes._under(text, scope)
        lines = [ln for ln in text.splitlines()
                 if (m := re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = ", ln))
                 and m.group(1) in names]
        seen = {"replay" if "rematted_computation" in ln
                else "bwd" if "transpose(" in ln else "fwd" for ln in lines}
        assert seen == {"fwd", "replay", "bwd"}, (scope, seen)
        # two kernels a kind lie under its scope
        assert sum("tpu_custom_call" in ln and "_flash" in ln
                   for ln in lines) == 2, scope


# three small decoders that take the paths of the Mistral, OLMoE and
# Ouro cells: knobs, the layer calls that keep their MLP products
_OLDER_DECODERS = {
    "dense": (dict(), 1),
    "dropless_moe": (
        dict(n_experts=8, moe_top_k=2, ffn_dim=256, capacity_factor=None,
             qk_norm=True, moe_renormalize=False, moe_z_coef=0.001), 0),
    "looped": (dict(ut_steps=3, exit_beta=0.1, sandwich_norm=True), 1),
}


def _text_census(text):
    """What a lowered step text is made of, in a form a failed
    comparison can show: its lines, how often each operation stands in
    it and how often each tensor type."""
    import collections
    import re

    count = lambda pattern: dict(sorted(collections.Counter(
        re.findall(pattern, text)).items()))
    return {"lines": len(text.splitlines()),
            "ops": count(r"\b(?:stablehlo|func|sdy|mhlo)\.[a-z_]+"),
            "types": count(r"tensor<[^>]*>")}


@pytest.mark.parametrize("name", _OLDER_DECODERS, ids=str)
def test_older_decoders_lower_to_the_text_they_had(chip, monkeypatch, name):
    """Latent attention, layer kinds, the sigmoid router, the held
    range, the selection bias and the MTP module are all behind knobs
    whose defaults leave the lowered step of a plain, a dropless
    expert and a looped decoder as it was: the census of each text
    (``tests/data/older_step_census.json``, taken on the parent of
    PR 37, 30388345, where the whole texts were equal, the kernels'
    serialized bodies and the lowering's numbering of its private
    functions apart; recorded anew in PR 45, which MEANT to move all
    three: grouped-query attention's products write the kernels'
    layout and the rotation is one pass, so the gathers, scatters and
    half-row arrays of the stride-2 slices went; and in PR 54, which
    MEANT to: one backward kernel a layer call where there were two,
    so a custom call, its operands' reshapes and the dQ kernel's
    tiles went from each) is what it was.
    After a change that is MEANT to move one of them, or another jax,
    the assertion shows what moved; record anew with
    ``_text_census``."""
    import json
    from pathlib import Path

    knobs, n_keep = _OLDER_DECODERS[name]
    text = _llama_step_text(chip, monkeypatch, n_keep=n_keep, lowered=True,
                            **knobs)
    want = json.loads(
        (Path(__file__).parent / "data" / "older_step_census.json")
        .read_text())[name]
    got = _text_census(text)
    assert got["ops"] == want["ops"]
    assert got["types"] == want["types"]
    assert got["lines"] == want["lines"]


@pytest.mark.parametrize("n_keep", [0, 1, 2])
def test_kept_calls_replay_no_gate_or_up_product(chip, monkeypatch, n_keep):
    """The same step with the last ``n_keep`` of its 2 layer calls
    keeping ``MLP_RESIDUALS``: the compiled text holds a gate and an
    up product (a ``[.., 512]`` result under ``blk_ffn``) in the
    replay of the calls that keep neither, and in no other; the two
    flash kernels a layer stay."""
    text = _llama_step_text(chip, monkeypatch, n_keep=n_keep)
    replayed = [
        ln for ln in text.splitlines()
        if " convolution(" in ln and "rematted_computation" in ln
        and "blk_ffn" in ln and ",512]" in ln.split(" convolution(")[0]
    ]
    assert len(replayed) == 2 * (2 - n_keep), replayed
    assert _flash_kernels(text) == dict(fwd=2, dkv=2)


@pytest.mark.parametrize("knobs, n_keep_attn", [
    (dict(), 0), (dict(), 1), (dict(), 2), (dict(qk_norm=True), 2),
    (dict(position_embedding_type="nope"), 2),
], ids=["rope-0", "rope-1", "rope-2", "qk_norm-2", "nope-2"])
def test_kept_attention_calls_replay_no_projection(
    chip, monkeypatch, knobs, n_keep_attn
):
    """A GQA 2:1 step with the last ``n_keep_attn`` of its 2 layer
    calls keeping ``ATTN_RESIDUALS``: the compiled text holds the
    three projections (products under ``gqa_proj`` at the default
    precision; the rotation's own is at the highest) and ``wo`` (a
    ``[B, T, D]`` result under ``blk_attn``) in the replay of the
    calls that keep none, and in no other; the two flash kernels a
    layer stay."""
    text = _llama_step_text(chip, monkeypatch, n_keep_attn=n_keep_attn,
                            dim=512, n_heads=4, n_kv_heads=2, **knobs)
    replayed = [
        ln for ln in text.splitlines()
        if " convolution(" in ln
        and "rematted_computation/blk_attn" in ln
    ]
    projections = [ln for ln in replayed if "/gqa_proj/" in ln
                   and "operand_precision={highest" not in ln]
    wo = [ln for ln in replayed if "/gqa_proj/" not in ln
          and "[2,256,512]" in ln.split(" convolution(")[0]]
    assert (len(projections), len(wo)) == (
        3 * (2 - n_keep_attn), 2 - n_keep_attn), replayed
    assert _flash_kernels(text) == dict(fwd=2, dkv=2)


def test_classifier_step_names_conv_and_batch_norm(chip, monkeypatch):
    """A three-block ``ClassifierModel`` (conv, batch norm, relu,
    pool; twice; pool, FC) through its own train step compiled for
    the v5e: ``blk_conv`` and ``blk_bn`` forward and backward (the
    scope around ``_bn_train``'s call reaches its backward rule),
    ``blk_pool``, ``blk_head`` with the loss, the optimizer."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from theanompi_tpu.models.base import ClassifierModel
    from theanompi_tpu.ops.layers import (BN, FC, Activation, Conv,
                                          GlobalAvgPool, Pool, Sequential)
    from theanompi_tpu.parallel import make_mesh

    class ThreeBlocks(ClassifierModel):
        def build_model(self, n_replicas=1):
            self.net = Sequential([
                Conv(128, 3, pad=1, bias=False), BN(), Activation("relu"),
                Pool(2),
                Conv(128, 3, pad=1, bias=False), BN(), Activation("relu"),
                GlobalAvgPool(), FC(10),
            ])
            self.input_shape = (32, 32, 8)
            self.data = None

    mesh = make_mesh(data=1, devices=list(chip.device_set))
    model = ThreeBlocks(dict(batch_size=16, compute_dtype="bfloat16",
                             optimizer="momentum"))
    model.build_model()
    # the parameters stay where they were made: a described device
    # holds nothing, and the step is lowered from their shapes
    monkeypatch.setattr(jax, "device_put", lambda x, *a, **k: x)
    model.compile_iter_fns(mesh=mesh)
    rep, dp = NamedSharding(mesh, P()), NamedSharding(mesh, P("data"))

    def shapes(tree, sharding=rep):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(
                jnp.shape(a), jnp.result_type(a), sharding=sharding), tree)

    x = jax.ShapeDtypeStruct((16, 32, 32, 8), jnp.float32, sharding=dp)
    y = jax.ShapeDtypeStruct((16,), jnp.int32, sharding=dp)
    lr = jax.ShapeDtypeStruct((), jnp.float32, sharding=rep)
    text = model._train_step.lower(
        shapes(model.params), shapes(model.net_state),
        shapes(model.opt_state), model.ef_state, x, y, lr,
        shapes(jax.random.PRNGKey(0)),
    ).compile().as_text()
    have, top, _, products = _step_blocks(text)
    expected = {
        (block, phase)
        for block in ("blk_conv", "blk_bn", "blk_pool", "blk_head")
        for phase in ("fwd", "bwd")
    } | {("opt_update", "fwd")}
    assert expected <= have, expected - have
    assert "replay" not in {p for _, p in have}
    # the convolutions (and the classifier's product) decide their
    # fusions' blocks, whatever XLA fused into them
    assert {e["block"] for e in products.values()} == {"blk_conv", "blk_head"}
    assert {e["phase"] for e in products.values()
            if e["block"] == "blk_conv"} == {"fwd", "bwd"}
    import re

    named = [e["op_name"] for e in top.values() if e["op_name"]]
    assert all(re.fullmatch(_GLUE, n) for n in named), named
    assert len(top) <= 10, sorted(top)


# -- query heads a layer, a gate a head, half a head rotated (PR 50) ---------

# the cell's kind of step at the suite's widths: a dense full layer of
# 2 heads, a window layer of 4 and an expert full layer of 2 over 2
# key/value heads of 128, each gated; the full ones rotate 64 channels
# under YaRN; 2 of 8 softmax-routed experts held, the gates times 2.5,
# a shared expert
_GATED = dict(
    n_layers=3, n_heads=2, n_kv_heads=2, head_dim=128,
    n_heads_per_layer=[2, 4, 2], attention_gate="per-head",
    layer_types=["full_attention", "sliding_attention", "full_attention"],
    sliding_window=128,
    rope_parameters={
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 500000, "factor": 128,
            "original_max_position_embeddings": 64, "beta_fast": 32,
            "beta_slow": 1, "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000},
    },
    n_experts=8, moe_top_k=2, ffn_dim=256, capacity_factor=None,
    moe_route_scale=2.5, moe_experts_held=2, moe_shared_experts=1,
    first_k_dense=1, dense_ffn_dim=512, moe_aux_coef=0.001,
)


def test_gated_step_compiles_with_each_kinds_heads_and_the_gates_scope(
    chip, monkeypatch
):
    """Compiled for the v5e: the full layers' flash calls at 2 heads a
    sequence and the window layer's at 4 (``[B H, T, hd]`` operands),
    each under its own jit's name; the scope ``attn_gate`` in the
    forward, the replay and the backward, inside ``blk_attn`` and the
    kind's scope and outside ``gqa_proj``; every block named."""
    import re

    from benchmark.layer_metrics import _scopes

    text = _llama_step_text(chip, monkeypatch, **_GATED)
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    full = [ln for ln in calls if "jit(_flash_jit)" in ln]
    band = [ln for ln in calls if "jit(_flash_window_jit)" in ln]
    assert len(full) == 2 * 2 and len(band) == 2
    assert all(re.search(r"bf16\[4,256,128\]", ln) for ln in full), full
    assert all(re.search(r"bf16\[8,256,128\]", ln) for ln in band), band
    assert all("attn_full" in ln for ln in full)
    assert all("attn_sliding" in ln for ln in band)
    names = _scopes._under(text, "attn_gate")
    lines = [ln for ln in text.splitlines()
             if (m := re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = ", ln))
             and m.group(1) in names]
    seen = {"replay" if "rematted_computation" in ln
            else "bwd" if "transpose(" in ln else "fwd" for ln in lines}
    assert seen == {"fwd", "replay", "bwd"}
    gated = [ln for ln in text.splitlines() if "/attn_gate/" in ln]
    assert gated and all("blk_attn" in ln for ln in gated)
    assert all("attn_full/attn_gate" in ln or "attn_sliding/attn_gate" in ln
               for ln in gated)
    assert not any("gqa_proj" in ln for ln in gated)
    have, top, kernels, products = _step_blocks(text)
    for block in ("blk_attn", "blk_ffn"):
        assert {(block, ph) for ph in ("fwd", "replay", "bwd")} <= have
    assert "other" not in {e["block"] for e in products.values()}


@pytest.mark.parametrize("knobs, spelled", [
    (dict(), dict(n_heads_per_layer=[2, 2], attention_gate=None)),
    (dict(n_experts=8, moe_top_k=2, ffn_dim=256, capacity_factor=None),
     dict(moe_route_scale=1.0, attention_gate=False)),
    (dict(layer_types=["sliding_attention", "full_attention"],
          sliding_window=128, head_dim=128,
          rope_parameters={
              "full_attention": {"rope_type": "default", "rope_theta": 1e4},
              "sliding_attention": {"rope_type": "default",
                                    "rope_theta": 1e4}}),
     dict(n_heads_per_layer=[2, 2], rope_parameters={
         "full_attention": {"rope_type": "default", "rope_theta": 1e4,
                            "partial_rotary_factor": 1},
         "sliding_attention": {"rope_type": "default", "rope_theta": 1e4,
                               "partial_rotary_factor": 1.0}})),
], ids=["dense", "softmax_moe", "kinds"])
def test_the_new_knobs_at_their_defaults_lower_the_same_step(
    chip, monkeypatch, knobs, spelled
):
    """``n_heads_per_layer`` at ``n_heads``, no gate, a routed scaling
    factor of 1 and a partial rotary factor of 1 are today's step:
    the lowered text does not change by a character (the older cells'
    texts at their real sizes were held to the parent's the same way
    when the knobs came; PERF.md section 6, PR 50)."""
    plain = _llama_step_text(chip, monkeypatch, lowered=True, **knobs)
    same = _llama_step_text(
        chip, monkeypatch, lowered=True, **dict(knobs, **spelled))
    assert plain == same
