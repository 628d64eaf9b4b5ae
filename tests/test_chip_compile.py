"""The main path's kernels compile for the chip — without the chip.

The TPU compiler ships with jaxlib and compiles for a DESCRIBED
topology (``v5e:2x2``), so Mosaic's refusals — a slice not aligned to
the tiling, a 16-bit matmul accumulator, too much VMEM — show up here
at no chip time, where the Pallas interpreter accepts anything.  Every
shape is one the Llama proxy of ``chip_smoke.py`` / ``bench.py`` runs:
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


# [B, H, T, hd] after the GQA repeat — what Llama._layer hands the
# kernel: the proxy (16 heads of 64) and its hd-128 variant (8 of 128)
FLASH_SHAPES = [(4, 16, 2048, 64), (4, 8, 2048, 128)]


@pytest.mark.parametrize("shape", FLASH_SHAPES, ids=str)
@pytest.mark.parametrize("direction", ["forward", "backward"])
def test_flash_attention_compiles_for_v5e(chip, shape, direction):
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=chip)

    def forward(q, k, v):
        return flash_attention_tpu(q, k, v, causal=True)

    def backward(q, k, v):
        return jax.grad(
            lambda *a: forward(*a).astype(jnp.float32).sum(),
            argnums=(0, 1, 2),
        )(q, k, v)

    fn = forward if direction == "forward" else backward
    assert "tpu_custom_call" in _compiled_text(fn, x, x, x)


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


@pytest.mark.parametrize("direction", ["forward", "backward"])
def test_dropless_expert_layer_compiles_to_grouped_kernels(chip, direction):
    """OLMoE's widths (2048 wide, 64 experts of 1024, 8 picks) at one
    sequence of 4096: the v5e compiler must turn every grouped product
    of ``moe_ffn``'s dropless path into a Mosaic kernel whose work
    follows the rows — three forward, nine with the backward — and
    keep no ``[E, N, D]`` capacity buffer and no product over all 64
    experts for every row (the 8x this path exists to avoid)."""
    import re

    from theanompi_tpu.parallel.moe import moe_ffn

    e, k, d, f, n = 64, 8, 2048, 1024, 4096

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    def forward(x, router, wg, wu, wd):
        y, aux = moe_ffn(
            x, router, wg, wu, wd, n_experts=e, top_k=k,
            capacity_factor=None, expert_axis=None, model_axis=None,
            renormalize=False,
        )
        return jnp.sum(y.astype(jnp.float32)) + aux["lb"] + aux["z"]

    fn = forward if direction == "forward" else jax.value_and_grad(
        forward, argnums=(0, 1, 2, 3, 4)
    )
    text = _compiled_text(
        fn, sds((1, n, d), jnp.bfloat16), sds((d, e), jnp.float32),
        sds((e, d, f), jnp.float32), sds((e, d, f), jnp.float32),
        sds((e, f, d), jnp.float32),
    )
    products = [
        line for line in text.splitlines()
        if "tpu_custom_call" in line and "ragged-dot" in line
        and "ragged-dot-metadata" not in line.split("=", 1)[0]
    ]
    assert len(products) == (3 if direction == "forward" else 9)
    # sorted rows [k*N, .] in, never a per-expert copy of the tokens
    assert all(re.search(rf"\[({k * n},({d}|{f})|{e},\d+,\d+)\]", p)
               for p in products)
    assert not re.search(rf"\[{e},{n},{d}\]|\[{e},{k * n},", text)
