"""model step (models/llama.py ``_mla_qkv``): device milliseconds a
step in instructions under ``mla_proj`` — latent attention's
projections between ``attn_norm`` and the flash kernels: the two
down-projections with their RMSNorms, the two up-projections, RoPE on
the rotary parts and the concatenation of a head's q and k — forward,
replay and backward, their weight-gradient products with the Adam
update XLA fused into them.  Part of ``attn_block_ms``."""
from ._scopes import scope_ms


def read(facts):
    return scope_ms(facts, "mla_proj")
