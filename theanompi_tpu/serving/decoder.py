"""Model layer of the serving engine: GQA-aware, tp-sharded KV-cache
decode for ``models/llama.py``.

Two cache organisations share one module:

- ``LlamaDecoder`` (v1) — slot-contiguous cache
  ``[slots, kv_heads/tp, max_seq, hd]``: every slot owns ``max_seq``
  HBM rows whether it uses them or not.
- ``PagedLlamaDecoder`` (v2) — paged cache: per-layer block POOLS
  ``[n_blocks + 1, kv_heads/tp, block_size, hd]`` plus per-slot BLOCK
  TABLES (``serving/blocks.py``); decode reads K/V through the table
  with a gather and writes through it with a scatter, so HBM is
  proportional to tokens actually cached, blocks are shareable
  (radix prefix cache, ``serving/prefix_cache.py``) and long prompts
  prefill in fixed-size CHUNKS interleaved with decode steps.  The
  decode executable's HLO shape depends only on
  (slots, max_blocks_per_slot, block_size) — table contents, chunk
  boundaries, sharing and copy-on-write are all DATA, so the
  one-compile discipline survives paging (``n_decode_compiles`` /
  ``n_prefill_compiles`` are the tested bounds).  The extra pool row
  is the TRASH block: inactive slots and padding rows write there,
  which keeps the executables branch-free.

Two fixed-shape jitted functions per decoder (the vLLM/Orca split):

- ``prefill`` — run one request's prompt through the full causal
  forward (the training ``flash_attention`` path, sp=1), write its
  K/V into the request's cache SLOT, and sample the first output
  token.  Prompt lengths are BUCKETED (padded up to the next bucket
  size) so the number of compiled prefill executables is bounded by
  the bucket count, not by the number of distinct prompt lengths.
- ``decode_step`` — one token for ALL slots at once: embed each
  slot's current token, append its K/V at the slot's position, attend
  over the slot's cached history, sample the next token.  Slots are
  mathematically independent rows (per-row matmuls, per-slot
  attention, per-slot PRNG keys folded with the token POSITION), so a
  request decoded in a full batch is bitwise-equal to the same
  request decoded alone — the property continuous batching needs to
  be a scheduling choice rather than a math choice.

Sharding: weights keep the training layout (``Llama.param_specs`` —
QKV/gate/up column-parallel, o/down row-parallel, vocab sharded
through embed/head); the KV cache shards its KV-HEAD dim over the
``model`` axis, so each tp shard caches exactly the heads it
computes.  The samplers (``parallel/tp.py``: ``sharded_argmax`` /
``sharded_sample``) combine over the model axis with the (value, id)
max-reduction trick and full-vocab Gumbel draws, which makes sampled
ids bitwise layout-invariant across tp=1 vs tp>1 meshes.

Everything runs in unchecked manual mode (``check_vma=False``) with
explicit collectives only.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from theanompi_tpu.models.llama import (
    Llama,
    _heads,
    _unheads,
    rms_norm,
    rope,
    rope_at,
)
from theanompi_tpu.ops.attention import NEG_INF, flash_attention
from theanompi_tpu.ops.layers import swiglu
from theanompi_tpu.parallel import (
    MODEL_AXIS,
    default_devices,
    dp_replicas,
    make_mesh,
)
from theanompi_tpu.parallel import tp as tp_lib
from theanompi_tpu.serving.blocks import BlockManager
from theanompi_tpu.serving.prefix_cache import PrefixCache


def default_prefill_buckets(max_prefill: int, base: int = 16) -> tuple:
    """Power-of-two bucket ladder ``base, 2*base, ...`` capped at
    ``max_prefill`` (always included) — one compile per bucket."""
    out = []
    b = base
    while b < max_prefill:
        out.append(b)
        b *= 2
    out.append(max_prefill)
    return tuple(out)


class LlamaDecoder:
    """KV-cache decoder over a compiled (and typically
    checkpoint-restored) ``Llama`` — see module docstring.

    The decoder owns the cache (``max_slots`` request slots of
    ``max_seq`` positions each) and exposes the two host-callable
    device functions the engine schedules:

    - ``prefill(slot, prompt_ids, key, temperature) -> first token``
    - ``decode(tokens, lengths, keys, temps) -> next tokens [S]``

    Serving composes with tensor parallelism only: ``pp > 1``,
    ``sp > 1``, MoE and QK-norm models are not yet servable.
    """

    paged = False

    def __init__(
        self,
        model: Llama,
        *,
        max_slots: int = 8,
        max_seq: int | None = None,
        prefill_buckets: tuple | None = None,
    ):
        self._init_common(model, max_slots, max_seq)
        self.prefill_buckets = tuple(
            sorted(prefill_buckets)
            if prefill_buckets else default_prefill_buckets(self.max_prefill)
        )
        assert self.prefill_buckets[-1] == self.max_prefill, (
            f"largest prefill bucket {self.prefill_buckets[-1]} must "
            f"equal max_prefill {self.max_prefill}"
        )

        m = model
        # KV cache: one {k, v} pair per layer, [S, Hkv/tp, T, hd] in
        # compute dtype, kv-head dim sharded over the model axis
        shape = (self.max_slots, m.n_kv_heads, self.max_seq, self._hd)
        self.cache = self._zeros_cache(shape)

    def _init_common(self, model: Llama, max_slots, max_seq) -> None:
        if model.mesh is None or model.params is None:
            raise ValueError(
                "LlamaDecoder needs a compiled model: call "
                "build_model() + compile_iter_fns() (then load() for "
                "checkpoint weights) before serving"
            )
        if (model.block_pattern is not None or model.head_share
                or model.hidden_act != "silu" or model.moe_latent_dim):
            raise NotImplementedError(
                "serving runs whole layers at whole head counts: blocks "
                f"of one sublayer (layer_types={model.block_pattern!r}) "
                "need a cache a block KIND, none for an expert block, a "
                "window and a state for a mixer; a head share "
                f"(head_share={model.head_share}) gives one rank's "
                "partial result, which no decoder here sums; two-product "
                f"experts (hidden_act={model.hidden_act!r}) and experts "
                f"in a latent (moe_latent_dim={model.moe_latent_dim}) are "
                "the expert layer's, which serving does not run — not "
                "yet servable"
            )
        if (model.attention == "mla" or model.moe_shared_experts
                or model.mtp_depth):
            raise NotImplementedError(
                "serving has one attention path and one cache: latent "
                f"attention (attention={model.attention!r}: a paged "
                "cache of the compressed latent and the shared rotary "
                "key, and prefill and decode paths of their own), "
                f"shared experts (moe_shared_experts="
                f"{model.moe_shared_experts}) and a multi-token-"
                f"prediction module (mtp_depth={model.mtp_depth}: "
                "speculative drafts from it) are not yet servable"
            )
        multipliers = (
            model.embedding_multiplier, model.residual_multiplier,
            model.logits_scaling, model.attention_multiplier,
        ) != (1.0, 1.0, 1.0, None)
        if (model.has_mamba or model.tie_word_embeddings or multipliers
                or model.position_embedding_type == "nope"):
            raise NotImplementedError(
                "serving has no recurrent state: a stack with a mamba "
                f"layer (mixer_kinds={model.mixer_kinds_count}) needs a "
                "convolution window and a scan state a layer and slot "
                "beside the KV blocks, two kinds of cache in one "
                "manager; nor does it know position_embedding_type "
                f"{model.position_embedding_type!r}, a tied head "
                f"(tie_word_embeddings={model.tie_word_embeddings}) or "
                "the embedding, residual, attention and logits "
                f"multipliers (given: {multipliers}) — not yet "
                "servable"
            )
        if (model.attention_gate or len(set(model.heads_per_layer)) > 1
                or set(model.rotary_channels.values()) != {model.head_dim}):
            raise NotImplementedError(
                "serving has one head count, no gate and whole-head "
                "rotation: query heads a layer (heads_per_layer="
                f"{model.heads_per_layer}) need a q projection, a GQA "
                "repeat and a paged-kernel call shaped a layer; an "
                f"attention gate (attention_gate={model.attention_gate}) "
                "a product and a sigmoid a head between the kernel and "
                "wo in prefill and decode; a partial rotary factor "
                f"(rotary_channels={model.rotary_channels}) a table "
                "narrower than the head in rope_at — not yet servable"
            )
        if getattr(model, "attn_per_layer", False):
            raise NotImplementedError(
                "serving has one cache lifetime and one rotary table: "
                f"a stack described layer by layer (attention_kinds="
                f"{model.attention_kinds}, sliding_window="
                f"{model.sliding_window}, rope_parameters "
                f"{'given' if model.rope_parameters else 'not given'}) "
                "needs a cache whose window layers free blocks behind "
                "the window, a paged kernel that walks the band, and a "
                "table a layer kind in prefill and decode — not yet "
                "servable"
            )
        if (model.pp > 1 or model.sp > 1 or model.n_experts
                or model.qk_norm):
            raise NotImplementedError(
                "serving composes with tensor parallelism only — "
                f"pp={model.pp}, sp={model.sp}, "
                f"n_experts={model.n_experts}, "
                f"qk_norm={model.qk_norm} are not yet servable"
            )
        if model.ut_steps > 1 or model.sandwich_norm:
            raise NotImplementedError(
                "serving knows neither a looped decoder (ut_steps="
                f"{model.ut_steps}: a KV cache per (pass, layer) and an "
                "exit rule) nor sandwich norms (sandwich_norm="
                f"{model.sandwich_norm}: an RMSNorm on each branch's "
                "output) — not yet servable"
            )
        self.model = model
        self.mesh = model.mesh
        self.max_slots = int(max_slots)
        self.max_seq = int(max_seq or model.seq_len)
        # decode appends one position past the prompt per token, so
        # the longest servable prompt leaves room for >= 1 new token
        self.max_prefill = self.max_seq - 1

        self._h_loc = model.n_heads // model.tp
        self._hkv_loc = model.n_kv_heads // model.tp
        self._rep = self._h_loc // self._hkv_loc
        self._hd = model.head_dim
        self._cdtype = model.compute_dtype
        # the training forward's helpers at the model's own constants
        self._norm = functools.partial(rms_norm, eps=model.norm_eps)
        self._rope = functools.partial(rope, theta=model.rope_theta)
        self._rope_at = functools.partial(rope_at, theta=model.rope_theta)
        kv_spec = P(None, MODEL_AXIS, None, None)
        self._cache_specs = [
            {"k": kv_spec, "v": kv_spec} for _ in range(model.n_layers)
        ]

        # compiled variants: decode keyed by the static all-greedy
        # flag, prefill by (bucket/chunk, greedy), the speculative
        # verify step by (k, greedy) — the compile count is bounded
        # by 2 x the shape-key count, a tested guarantee
        self._decode_fns: dict[bool, object] = {}
        self._prefill_fns: dict[tuple[int, bool], object] = {}
        self._verify_fns: dict[tuple[int, bool], object] = {}

    def _zeros_cache(self, shape):
        """Per-layer {k, v} zeros of ``shape``, kv-head dim sharded
        over the model axis (used for the contiguous cache AND the
        paged block pools — only the shape differs)."""
        sharding = NamedSharding(self.mesh, P(None, MODEL_AXIS, None, None))

        def _zeros():
            z = jnp.zeros(shape, self._cdtype)
            return [{"k": z, "v": z} for _ in range(self.model.n_layers)]

        return jax.jit(
            _zeros,
            out_shardings=[
                {"k": sharding, "v": sharding}
                for _ in range(self.model.n_layers)
            ],
        )()

    # -- device bodies (run on LOCAL shards inside shard_map) -------------

    def _mlp(self, p, x):
        xn = self._norm(x, p["mlp_norm"])
        h = swiglu(
            tp_lib.col_parallel(xn, p["w_gate"]),
            tp_lib.col_parallel(xn, p["w_up"]),
        )
        return x + tp_lib.row_parallel(h, p["w_down"]).astype(x.dtype)

    def _sample(self, logits, keys, pos, temps, greedy: bool):
        """Token ids from [N, V/tp] logits.  ``greedy=True`` is the
        static all-greedy fast path: pure ``sharded_argmax``, no
        Gumbel draw, no key fold — bitwise-identical ids to the
        sampling path at temperature<=0 (both argmax the same f32
        logits), so batch composition never changes outputs.

        Wrapped in a ``serving_sample`` named scope so its fused HLO
        is attributable from profiler traces (PR 4's
        ``trace_comm.scope_op_names`` technique — the bench's
        sampler-cost datum)."""
        with jax.named_scope("serving_sample"):
            if greedy:
                return tp_lib.sharded_argmax(
                    logits.astype(jnp.float32), self.model.vocab
                )
            # the token that will sit at position pos+1 samples with
            # fold_in(request_key, pos+1) — position-keyed, so batched
            # and single-request decodes draw identical noise
            skeys = jax.vmap(jax.random.fold_in)(keys, pos + 1)
            return tp_lib.sharded_sample(
                logits, self.model.vocab, skeys, temps
            )

    def _decode_body(self, params, cache, tokens, lengths, keys, temps,
                     greedy: bool):
        """One token for all slots.  tokens/lengths [S] int32, keys
        [S, 2] uint32, temps [S] f32 -> (cache, next_tokens [S])."""
        m = self.model
        s = self.max_slots
        hd, h_loc, hkv_loc, rep = (
            self._hd, self._h_loc, self._hkv_loc, self._rep
        )
        x = tp_lib.embed_lookup(
            tokens[:, None], params["embed"], m.vocab
        )[:, 0, :].astype(self._cdtype)                       # [S, D]
        pos = lengths                          # write position per slot
        valid = (
            jnp.arange(self.max_seq)[None, :] <= pos[:, None]
        )[:, None, None, :]                            # [S, 1, 1, T]

        new_cache = []
        for layer_cache, p in zip(cache, params["layers"]):
            xn = self._norm(x, p["attn_norm"])
            q = tp_lib.col_parallel(xn, p["wq"]).reshape(s, h_loc, hd)
            k = tp_lib.col_parallel(xn, p["wk"]).reshape(s, hkv_loc, hd)
            v = tp_lib.col_parallel(xn, p["wv"]).reshape(s, hkv_loc, hd)
            q = self._rope_at(q, pos)
            k = self._rope_at(k, pos)
            # append this token's K/V at each slot's own position
            write = jax.vmap(
                lambda c, u, i: lax.dynamic_update_slice(
                    c, u[:, None, :], (0, i, 0)
                )
            )
            ck = write(layer_cache["k"], k.astype(self._cdtype), pos)
            cv = write(layer_cache["v"], v.astype(self._cdtype), pos)
            new_cache.append({"k": ck, "v": cv})
            # GQA attention against the cached history: group the
            # query heads by their KV head, no repeat materialized
            qg = q.reshape(s, hkv_loc, rep, hd)
            scores = jnp.einsum("skrd,sktd->skrt", qg, ck).astype(
                jnp.float32
            ) * (hd ** -0.5)
            scores = jnp.where(valid, scores, NEG_INF)
            probs = jax.nn.softmax(scores, axis=-1)
            o = jnp.einsum(
                "skrt,sktd->skrd", probs.astype(cv.dtype), cv
            ).reshape(s, h_loc * hd)
            x = x + tp_lib.row_parallel(o, p["wo"]).astype(self._cdtype)
            x = self._mlp(p, x)

        xf = self._norm(x, params["final_norm"])
        logits = tp_lib.col_parallel(xf, params["lm_head"])  # [S, V/tp]
        nxt = self._sample(logits, keys, pos, temps, greedy)
        return new_cache, nxt

    def _prefill_body(self, params, cache, ids, slot, length, key, temp,
                      greedy: bool):
        """Prompt forward for ONE request: ids [t_bucket] int32
        (zero-padded past ``length``), slot/length scalars.  Writes
        K/V rows [0, t_bucket) of ``slot`` (rows >= length hold
        padding garbage, but decode overwrites position p before any
        token attends to it — positions are filled strictly in order)
        and samples the first output token at position ``length``."""
        m = self.model
        hd, h_loc, hkv_loc, rep = (
            self._hd, self._h_loc, self._hkv_loc, self._rep
        )
        t = ids.shape[0]
        x = tp_lib.embed_lookup(
            ids[None, :], params["embed"], m.vocab
        ).astype(self._cdtype)                              # [1, t, D]
        pos = jnp.arange(t)

        new_cache = []
        for layer_cache, p in zip(cache, params["layers"]):
            xn = self._norm(x, p["attn_norm"])
            q = _heads(tp_lib.col_parallel(xn, p["wq"]), h_loc, hd)
            k = _heads(tp_lib.col_parallel(xn, p["wk"]), hkv_loc, hd)
            v = _heads(tp_lib.col_parallel(xn, p["wv"]), hkv_loc, hd)
            q = self._rope(q, pos)
            k = self._rope(k, pos)
            kc = k.astype(self._cdtype)
            vc = v.astype(self._cdtype)
            new_cache.append({
                "k": lax.dynamic_update_slice(
                    layer_cache["k"], kc, (slot, 0, 0, 0)
                ),
                "v": lax.dynamic_update_slice(
                    layer_cache["v"], vc, (slot, 0, 0, 0)
                ),
            })
            if rep != 1:
                k = jnp.repeat(k, rep, axis=1)
                v = jnp.repeat(v, rep, axis=1)
            o = flash_attention(q, k, v, causal=True)
            x = x + tp_lib.row_parallel(
                _unheads(o), p["wo"]
            ).astype(self._cdtype)
            x = self._mlp(p, x)

        xf = self._norm(x, params["final_norm"])
        # only the LAST PROMPT TOKEN's logits matter — slice before
        # the head so the [t, V] logits never materialize
        x_last = lax.dynamic_slice(
            xf, (0, length - 1, 0), (1, 1, xf.shape[-1])
        )[:, 0, :]                                          # [1, D]
        logits = tp_lib.col_parallel(x_last, params["lm_head"])
        # the first generated token sits at position `length`:
        # _sample folds pos+1, so pass length-1 (same fold policy as
        # decode — token at position p always draws fold_in(key, p))
        tok = self._sample(
            logits, key[None], jnp.reshape(length - 1, (1,)),
            temp[None], greedy,
        )[0]
        return new_cache, tok

    # -- compiled entry points --------------------------------------------

    def _decode_jit(self, greedy: bool):
        fn = self._decode_fns.get(greedy)
        if fn is None:
            import functools

            rep = P()
            fn = jax.jit(
                jax.shard_map(
                    functools.partial(self._decode_body, greedy=greedy),
                    mesh=self.mesh,
                    in_specs=(self.model._specs, self._cache_specs,
                              rep, rep, rep, rep),
                    out_specs=(self._cache_specs, rep),
                    check_vma=False,
                ),
                donate_argnums=(1,),
            )
            self._decode_fns[greedy] = fn
        return fn

    def _prefill_jit(self, bucket: int, greedy: bool):
        fn = self._prefill_fns.get((bucket, greedy))
        if fn is None:
            import functools

            rep = P()
            fn = jax.jit(
                jax.shard_map(
                    functools.partial(
                        self._prefill_body, greedy=greedy
                    ),
                    mesh=self.mesh,
                    in_specs=(self.model._specs, self._cache_specs,
                              rep, rep, rep, rep, rep),
                    out_specs=(self._cache_specs, rep),
                    check_vma=False,
                ),
                donate_argnums=(1,),
            )
            self._prefill_fns[(bucket, greedy)] = fn
        return fn

    def bucket_for(self, prompt_len: int) -> int:
        """Smallest compiled-shape bucket covering ``prompt_len``."""
        if not 1 <= prompt_len <= self.max_prefill:
            raise ValueError(
                f"prompt length {prompt_len} outside servable range "
                f"[1, {self.max_prefill}] (max_seq {self.max_seq} "
                f"leaves one position for generation)"
            )
        for b in self.prefill_buckets:
            if b >= prompt_len:
                return b
        raise AssertionError("unreachable: last bucket == max_prefill")

    # -- host API (the engine's two scheduling primitives) ----------------

    def prefill(self, slot: int, prompt_ids, key, temperature) -> int:
        """Run one prompt into ``slot``; returns the first sampled
        token (host int — reading it IS the TTFT fence)."""
        ids = np.asarray(prompt_ids, np.int32)
        bucket = self.bucket_for(ids.shape[0])
        padded = np.zeros((bucket,), np.int32)
        padded[: ids.shape[0]] = ids
        self.cache, tok = self._prefill_jit(bucket, temperature <= 0)(
            self.model.params, self.cache,
            jnp.asarray(padded),
            jnp.int32(slot), jnp.int32(ids.shape[0]),
            jnp.asarray(key, jnp.uint32),
            jnp.float32(temperature),
        )
        return int(tok)

    def decode(self, tokens, lengths, keys, temps) -> np.ndarray:
        """One decode step for all slots.  Host arrays in, host token
        ids [S] out (the read fences the step).  An all-greedy batch
        (the common case) dispatches the Gumbel-free executable; a
        mixed batch uses the sampling one, whose per-slot
        temperature<=0 branch argmaxes identically."""
        self.cache, nxt = self._decode_jit(
            bool(np.all(np.asarray(temps) <= 0.0))
        )(
            self.model.params, self.cache,
            jnp.asarray(tokens, jnp.int32),
            jnp.asarray(lengths, jnp.int32),
            jnp.asarray(keys, jnp.uint32),
            jnp.asarray(temps, jnp.float32),
        )
        return np.asarray(nxt)

    @property
    def n_prefill_compiles(self) -> int:
        """Compiled prefill variants so far (bounded by 2 x the
        bucket ladder: (bucket, greedy) keys — the compile-count
        guarantee under test)."""
        return len(self._prefill_fns)

    @property
    def n_decode_compiles(self) -> int:
        """Compiled decode-phase variants so far — plain decode AND
        speculative verify executables.  Each family is bounded by 2
        (greedy fast path + sampling), and one ENGINE dispatches one
        family (plain decode, or verify at its fixed ``k``), so the
        count never grows with batch composition, table contents,
        draft contents, or offered load — the bench sweep asserts
        ≤ 2 in-child.  A decoder shared by speculative AND
        non-speculative engines under mixed temperatures can
        legitimately reach 4 (both families, both sampling modes);
        what is bounded is the set of shapes, never per-request
        recompiles."""
        return len(self._decode_fns) + len(self._verify_fns)

    def kv_cache_bytes(self) -> int:
        """Total HBM the KV cache occupies (all layers, global across
        tp shards)."""
        m = self.model
        itemsize = jnp.dtype(self._cdtype).itemsize
        return (
            2 * m.n_layers * self.max_slots * m.n_kv_heads
            * self.max_seq * self._hd * itemsize
        )

    def kv_bytes_per_slot(self) -> int:
        """HBM one admitted request costs — for the contiguous cache,
        ``max_seq`` rows regardless of how many it uses (the paged
        decoder's version is proportional to blocks actually held)."""
        return self.kv_cache_bytes() // self.max_slots

    def _dummy_decode_args(self) -> tuple:
        s = self.max_slots
        return (
            self.model.params, self.cache,
            jnp.zeros((s,), jnp.int32), jnp.zeros((s,), jnp.int32),
            jnp.zeros((s, 2), jnp.uint32), jnp.zeros((s,), jnp.float32),
        )

    def decode_hlo_text(self, greedy: bool = True) -> str:
        """Optimized-HLO text of the decode executable (one AOT
        lower/compile — not served from the jit call cache, so fetch
        it once and scan for every marker set you need)."""
        from theanompi_tpu.utils.trace_comm import compiled_hlo_text

        lowered = self._decode_jit(greedy).lower(
            *self._dummy_decode_args()
        )
        return compiled_hlo_text(lowered.compile())

    def decode_scope_op_names(
        self, markers: tuple, greedy: bool = True
    ) -> set:
        """HLO instruction names of the decode executable whose
        name-stack mentions any of ``markers`` (``serving_sample``,
        ``paged_attend``, ``kv_write``) — feed to
        ``trace_comm.comm_report(quant_ops=...)`` to attribute their
        share of a traced decode run (the sampler/attention cost
        split the bench's serving row reports)."""
        from theanompi_tpu.utils.trace_comm import scope_op_names

        return scope_op_names(
            self.decode_hlo_text(greedy), markers=tuple(markers)
        )


class PagedLlamaDecoder(LlamaDecoder):
    """Paged-KV-cache decoder (serving v2): block pools + per-slot
    block tables instead of a slot-contiguous cache.

    - K/V live in per-layer POOLS ``[n_blocks + 1, Hkv/tp,
      block_size, hd]`` (the ``+1`` row is the TRASH block — padding
      and inactive-slot writes land there, never read unmasked).
    - Each slot's BLOCK TABLE (``[max_blocks]`` int32, padded with
      the trash id) maps logical block index → physical block.
      Decode WRITES through the table with a scatter and READS with
      a gather, so the executable's HLO shape depends only on
      (max_slots, max_blocks, block_size): sharing, copy-on-write
      and chunked prefill are all table DATA.
    - Prefill runs in fixed-size CHUNKS of ``prefill_chunk`` token
      positions through ONE executable shape: ``prefill(table_row,
      ids, start, q_len, key, temp)`` processes the prompt span
      ``[start, start + q_len)`` against the already-cached history
      (adopted prefix blocks included) — the engine interleaves
      chunks with decode steps so a long arrival never stalls
      in-flight TPOT.  One executable shape also makes chunked ==
      monolithic and prefix-hit == cold bitwise: a token row's
      compute depends only on its own (token, position, cached
      prefix), never on its neighbours in the chunk.

    The bitwise guarantees of v1 survive: sampled ids are identical
    tp=1 vs tp=2 (vocab-sharded samplers), batched == single-request
    (slots are independent rows reading only their own blocks), and
    the greedy fast path still dispatches a Gumbel-free executable.

    Block bookkeeping (``self.manager``) and the radix prefix cache
    (``self.prefix_cache`` — shared across engines over this
    decoder, as warm cache state should be) are host-side; the
    engine drives admission, CoW, growth and eviction through them.
    """

    paged = True

    def __init__(
        self,
        model: Llama,
        *,
        max_slots: int = 8,
        max_seq: int | None = None,
        block_size: int = 16,
        n_blocks: int | None = None,
        prefill_chunk: int | None = None,
        prefix_cache: bool = True,
        paged_attend_impl: str = "gather",
        pallas_interpret: bool = False,
    ):
        from theanompi_tpu.serving.paged_attention import IMPLS

        self._init_common(model, max_slots, max_seq)
        if paged_attend_impl not in IMPLS:
            raise ValueError(
                f"paged_attend_impl must be one of {IMPLS}, got "
                f"{paged_attend_impl!r}"
            )
        # "gather" = the jnp block-table gather (the reference
        # oracle); "pallas" = the fused kernel
        # (serving/paged_attention.py), compiled through Mosaic.
        # pallas_interpret=True runs it in the Pallas interpreter
        # instead (the CPU tests); it is never chosen for the caller
        self.paged_attend_impl = paged_attend_impl
        self._pallas_interpret = bool(pallas_interpret)
        platform = self.mesh.devices.flat[0].platform
        if (paged_attend_impl == "pallas" and not self._pallas_interpret
                and platform != "tpu"):
            raise ValueError(
                f"paged_attend_impl='pallas' compiles through Mosaic "
                f"and this decoder's mesh is on {platform!r} devices "
                f"— pass pallas_interpret=True to run the kernel in "
                f"the Pallas interpreter, or use "
                f"paged_attend_impl='gather'"
            )
        self.block_size = int(block_size)
        self.manager = BlockManager(
            n_blocks=None if n_blocks is None else int(n_blocks),
            block_size=self.block_size,
            max_slots=self.max_slots, max_seq=self.max_seq,
        )
        # the manager owns the table-width derivation; executable
        # shapes (gather padding, dummy args) adopt it
        self.max_blocks = self.manager.max_blocks
        self.trash_id = self.manager.trash_id
        self.prefix_cache = (
            PrefixCache(self.manager.allocator) if prefix_cache else None
        )
        self.prefill_chunk = int(
            prefill_chunk if prefill_chunk is not None
            else min(64, self.max_prefill)
        )
        assert 1 <= self.prefill_chunk <= self.max_prefill

        m = model
        shape = (self.manager.allocator.n_blocks + 1, m.n_kv_heads,
                 self.block_size, self._hd)
        self.pools = self._zeros_cache(shape)
        self._copy_fn = None
        self._xfer_gather_fn = None
        self._xfer_scatter_fn = None

    # -- device bodies -----------------------------------------------------

    def _write_kv(self, pool, k, v, bids, offs):
        """Scatter per-row K/V ``[N, Hkv/tp, hd]`` into the pools at
        (block id, offset) per row.  Rows routed to the trash block
        may collide — their content is never read unmasked, so the
        scatter order is irrelevant to outputs."""
        with jax.named_scope("kv_write"):
            return {
                "k": pool["k"].at[bids, :, offs, :].set(
                    k.astype(self._cdtype)
                ),
                "v": pool["v"].at[bids, :, offs, :].set(
                    v.astype(self._cdtype)
                ),
            }

    def _gather_kv(self, pool, tables):
        """Block-table read: ``tables`` [..., MB] int32 → K/V
        [..., Hkv/tp, MB * block_size, hd] in position order."""
        mb, bs = self.max_blocks, self.block_size

        def one(arr):
            g = arr[tables]            # [..., MB, Hkv, bs, hd]
            if tables.ndim == 2:
                g = g.transpose(0, 2, 1, 3, 4)
                return g.reshape(
                    g.shape[0], self._hkv_loc, mb * bs, self._hd
                )
            g = g.transpose(1, 0, 2, 3)
            return g.reshape(self._hkv_loc, mb * bs, self._hd)

        return one(pool["k"]), one(pool["v"])

    def _paged_attend(self, lp, tables, q, pos):
        """Block-table attention for Q query rows per slot: ``q``
        [S, Q, h_loc, hd], ``pos`` [S, Q] (row (s, j) attends
        positions <= pos[s, j]) → o [S, Q, h_loc*hd].  ONE copy of
        the attend math for decode (Q=1) and the speculative verify
        step (Q=k); ``paged_attend_impl`` selects the jnp gather
        reference or the fused Pallas kernel
        (serving/paged_attention.py) — bitwise-equal for fp32, which
        is what makes the gather path the kernel's testable oracle."""
        s, nq = q.shape[:2]
        hd, hkv_loc, rep = self._hd, self._hkv_loc, self._rep
        t_pad = self.max_blocks * self.block_size
        with jax.named_scope("paged_attend"):
            qg = q.reshape(s, nq, hkv_loc, rep, hd)
            if self.paged_attend_impl == "pallas":
                from theanompi_tpu.serving.paged_attention import (
                    paged_attend,
                )

                o = paged_attend(
                    qg, lp["k"], lp["v"], tables, pos,
                    interpret=self._pallas_interpret,
                )
            else:
                kg, vg = self._gather_kv(lp, tables)
                valid = (
                    jnp.arange(t_pad)[None, None, :] <= pos[:, :, None]
                )[:, :, None, None, :]               # [S, Q, 1, 1, T]
                scores = jnp.einsum(
                    "sjkrd,sktd->sjkrt", qg, kg
                ).astype(jnp.float32) * (hd ** -0.5)
                scores = jnp.where(valid, scores, NEG_INF)
                probs = jax.nn.softmax(scores, axis=-1)
                # prob-weighted V as broadcast-mult + reduce over t
                # (NOT a dot_general): XLA's batched matvec lowering
                # reassociates the t-reduction when the row dim
                # degenerates to 1 (tp=8's hkv=rep=1 decode), which
                # would break fp32-bitwise equality with the Pallas
                # kernel's per-cell compute — reduce lowering is
                # association-stable across batching, matmul is not
                o = jnp.sum(
                    probs.astype(vg.dtype)[..., None]
                    * vg[:, None, :, None, :, :],
                    axis=-2,
                )
            return o.reshape(s, nq, self._h_loc * hd)

    def _decode_body(self, params, pools, tables, tokens, lengths,
                     keys, temps, active, greedy: bool):
        """One token for all slots through the block tables.
        tables [S, MB] int32, active [S] bool (False → writes routed
        to trash, outputs ignored by the engine); everything else as
        v1."""
        m = self.model
        s = self.max_slots
        bs = self.block_size
        hd, h_loc, hkv_loc = self._hd, self._h_loc, self._hkv_loc
        x = tp_lib.embed_lookup(
            tokens[:, None], params["embed"], m.vocab
        )[:, 0, :].astype(self._cdtype)                       # [S, D]
        pos = lengths                          # write position per slot
        bidx = jnp.clip(pos // bs, 0, self.max_blocks - 1)
        wbid = jnp.where(
            active, tables[jnp.arange(s), bidx], self.trash_id
        )
        woff = pos % bs

        new_pools = []
        for layer_pool, p in zip(pools, params["layers"]):
            xn = self._norm(x, p["attn_norm"])
            q = tp_lib.col_parallel(xn, p["wq"]).reshape(s, h_loc, hd)
            k = tp_lib.col_parallel(xn, p["wk"]).reshape(s, hkv_loc, hd)
            v = tp_lib.col_parallel(xn, p["wv"]).reshape(s, hkv_loc, hd)
            q = self._rope_at(q, pos)
            k = self._rope_at(k, pos)
            lp = self._write_kv(layer_pool, k, v, wbid, woff)
            new_pools.append(lp)
            o = self._paged_attend(
                lp, tables, q[:, None], pos[:, None]
            )[:, 0]
            x = x + tp_lib.row_parallel(o, p["wo"]).astype(self._cdtype)
            x = self._mlp(p, x)

        xf = self._norm(x, params["final_norm"])
        logits = tp_lib.col_parallel(xf, params["lm_head"])  # [S, V/tp]
        nxt = self._sample(logits, keys, pos, temps, greedy)
        return new_pools, nxt

    def _verify_body(self, params, pools, tables, tokens, lengths,
                     keys, temps, n_valid, greedy: bool):
        """Speculative VERIFY step: ``k`` tokens for all slots in one
        fixed-shape executable (the multi-token sibling of
        ``_decode_body``).

        ``tokens`` [S, K] int32 — column 0 is the slot's committed
        current token (what ``decode`` would consume), columns 1..K-1
        the drafter's proposals; ``lengths`` [S] the write position
        of column 0; ``n_valid`` [S] int32 in [0, K] — columns >=
        n_valid route their K/V writes to the trash block (0 = the
        slot is inactive; over-provisioned draft writes are maskable
        by the same discipline).  Returns (pools, out [S, K]) where
        ``out[s, j]`` is the token the model emits after consuming
        ``tokens[s, :j+1]`` — row j's compute is exactly what
        ``decode`` would compute at position ``lengths[s]+j`` with
        that prefix committed (same per-row matmuls, same position
        mask, same fold-by-position sampling), which is what makes
        accept-by-equality bitwise-equivalent to sequential decode.

        Rejected drafts need no explicit rollback: positions past the
        first rejection hold garbage K/V, but the accept logic
        commits the engine's lengths BELOW them, and the next verify
        window's writes cover every garbage position before any query
        row's mask can reach it (writes precede the gather within
        each layer)."""
        m = self.model
        s, kq = tokens.shape
        bs = self.block_size
        hd, h_loc, hkv_loc = self._hd, self._h_loc, self._hkv_loc
        x = tp_lib.embed_lookup(
            tokens, params["embed"], m.vocab
        ).astype(self._cdtype)                             # [S, K, D]
        pos = lengths[:, None] + jnp.arange(kq)[None, :]     # [S, K]
        in_range = jnp.arange(kq)[None, :] < n_valid[:, None]
        bidx = jnp.clip(pos // bs, 0, self.max_blocks - 1)
        wbid = jnp.where(
            in_range, jnp.take_along_axis(tables, bidx, axis=1),
            self.trash_id,
        )                                                    # [S, K]
        woff = pos % bs
        pos_f = pos.reshape(-1)

        def flat(a):
            return a.reshape(s * kq, *a.shape[2:])

        new_pools = []
        for layer_pool, p in zip(pools, params["layers"]):
            xn = self._norm(x, p["attn_norm"])
            q = tp_lib.col_parallel(xn, p["wq"]).reshape(
                s, kq, h_loc, hd
            )
            k = tp_lib.col_parallel(xn, p["wk"]).reshape(
                s, kq, hkv_loc, hd
            )
            v = tp_lib.col_parallel(xn, p["wv"]).reshape(
                s, kq, hkv_loc, hd
            )
            # rope_at over the flattened rows: per-row rotation at
            # the row's own position, the same vmap decode uses
            q = self._rope_at(flat(q), pos_f).reshape(s, kq, h_loc, hd)
            k = self._rope_at(flat(k), pos_f).reshape(s, kq, hkv_loc, hd)
            lp = self._write_kv(
                layer_pool, flat(k), flat(v),
                wbid.reshape(-1), woff.reshape(-1),
            )
            new_pools.append(lp)
            o = self._paged_attend(lp, tables, q, pos)   # [S,K,Hl*hd]
            x = x + tp_lib.row_parallel(o, p["wo"]).astype(self._cdtype)
            x = self._mlp(p, x)

        xf = self._norm(x, params["final_norm"])
        logits = tp_lib.col_parallel(xf, params["lm_head"])
        keys_f = jnp.broadcast_to(
            keys[:, None, :], (s, kq, 2)
        ).reshape(s * kq, 2)
        temps_f = jnp.broadcast_to(temps[:, None], (s, kq)).reshape(-1)
        nxt = self._sample(
            logits.reshape(s * kq, -1), keys_f, pos_f, temps_f, greedy
        ).reshape(s, kq)
        return new_pools, nxt

    def _prefill_body(self, params, pools, table_row, ids, start,
                      q_len, key, temp, greedy: bool):
        """One prefill CHUNK for one request: ids [C] int32
        (zero-padded past ``q_len``) occupy absolute positions
        ``[start, start + q_len)``; K/V rows scatter through
        ``table_row`` [MB]; attention reads the gathered history
        (adopted prefix blocks + earlier chunks + this chunk) under
        an absolute-position causal mask.  Samples the token that
        follows position ``start + q_len - 1`` — meaningful only on
        the final chunk (the engine discards the rest)."""
        m = self.model
        bs = self.block_size
        t_pad = self.max_blocks * bs
        hd, h_loc, hkv_loc, rep = (
            self._hd, self._h_loc, self._hkv_loc, self._rep
        )
        c = ids.shape[0]
        x = tp_lib.embed_lookup(
            ids[None, :], params["embed"], m.vocab
        )[0].astype(self._cdtype)                             # [C, D]
        pos = start + jnp.arange(c)
        in_range = jnp.arange(c) < q_len
        bidx = jnp.clip(pos // bs, 0, self.max_blocks - 1)
        wbid = jnp.where(in_range, table_row[bidx], self.trash_id)
        woff = pos % bs
        valid = (
            jnp.arange(t_pad)[None, :] <= pos[:, None]
        )[:, None, None, :]                            # [C, 1, 1, T]

        new_pools = []
        for layer_pool, p in zip(pools, params["layers"]):
            xn = self._norm(x, p["attn_norm"])
            q = tp_lib.col_parallel(xn, p["wq"]).reshape(c, h_loc, hd)
            k = tp_lib.col_parallel(xn, p["wk"]).reshape(c, hkv_loc, hd)
            v = tp_lib.col_parallel(xn, p["wv"]).reshape(c, hkv_loc, hd)
            q = self._rope_at(q, pos)
            k = self._rope_at(k, pos)
            lp = self._write_kv(layer_pool, k, v, wbid, woff)
            new_pools.append(lp)
            with jax.named_scope("paged_attend"):
                kg, vg = self._gather_kv(lp, table_row)  # [Hkv, T, hd]
                qg = q.reshape(c, hkv_loc, rep, hd)
                scores = jnp.einsum("ckrd,ktd->ckrt", qg, kg).astype(
                    jnp.float32
                ) * (hd ** -0.5)
                scores = jnp.where(
                    valid.reshape(c, 1, 1, t_pad), scores, NEG_INF
                )
                probs = jax.nn.softmax(scores, axis=-1)
                o = jnp.einsum(
                    "ckrt,ktd->ckrd", probs.astype(vg.dtype), vg
                ).reshape(c, h_loc * hd)
            x = x + tp_lib.row_parallel(o, p["wo"]).astype(self._cdtype)
            x = self._mlp(p, x)

        xf = self._norm(x, params["final_norm"])
        # only the chunk's LAST VALID row matters for sampling
        x_last = lax.dynamic_slice(
            xf, (q_len - 1, 0), (1, xf.shape[-1])
        )                                                   # [1, D]
        logits = tp_lib.col_parallel(x_last, params["lm_head"])
        # the next token sits at position start + q_len: _sample
        # folds pos+1, so pass start + q_len - 1 (same policy as
        # decode and the v1 prefill)
        tok = self._sample(
            logits, key[None], jnp.reshape(start + q_len - 1, (1,)),
            temp[None], greedy,
        )[0]
        return new_pools, tok

    # -- compiled entry points ---------------------------------------------

    def _decode_jit(self, greedy: bool):
        fn = self._decode_fns.get(greedy)
        if fn is None:
            import functools

            rep = P()
            fn = jax.jit(
                jax.shard_map(
                    functools.partial(self._decode_body, greedy=greedy),
                    mesh=self.mesh,
                    in_specs=(self.model._specs, self._cache_specs,
                              rep, rep, rep, rep, rep, rep),
                    out_specs=(self._cache_specs, rep),
                    check_vma=False,
                ),
                donate_argnums=(1,),
            )
            self._decode_fns[greedy] = fn
        return fn

    def _prefill_jit(self, greedy: bool):
        fn = self._prefill_fns.get((self.prefill_chunk, greedy))
        if fn is None:
            import functools

            rep = P()
            fn = jax.jit(
                jax.shard_map(
                    functools.partial(
                        self._prefill_body, greedy=greedy
                    ),
                    mesh=self.mesh,
                    in_specs=(self.model._specs, self._cache_specs,
                              rep, rep, rep, rep, rep, rep),
                    out_specs=(self._cache_specs, rep),
                    check_vma=False,
                ),
                donate_argnums=(1,),
            )
            self._prefill_fns[(self.prefill_chunk, greedy)] = fn
        return fn

    def _verify_jit(self, k: int, greedy: bool):
        fn = self._verify_fns.get((k, greedy))
        if fn is None:
            import functools

            rep = P()
            fn = jax.jit(
                jax.shard_map(
                    functools.partial(self._verify_body, greedy=greedy),
                    mesh=self.mesh,
                    in_specs=(self.model._specs, self._cache_specs,
                              rep, rep, rep, rep, rep, rep),
                    out_specs=(self._cache_specs, rep),
                    check_vma=False,
                ),
                donate_argnums=(1,),
            )
            self._verify_fns[(k, greedy)] = fn
        return fn

    def _copy_jit(self):
        if self._copy_fn is None:
            def body(pools, src, dst):
                return [
                    {
                        "k": lp["k"].at[dst].set(lp["k"][src]),
                        "v": lp["v"].at[dst].set(lp["v"][src]),
                    }
                    for lp in pools
                ]

            rep = P()
            self._copy_fn = jax.jit(
                jax.shard_map(
                    body, mesh=self.mesh,
                    in_specs=(self._cache_specs, rep, rep),
                    out_specs=self._cache_specs,
                    check_vma=False,
                ),
                donate_argnums=(0,),
            )
        return self._copy_fn

    def _gather_blocks_jit(self):
        """[max_blocks] int32 block ids → per-layer {k, v} GLOBAL
        arrays [max_blocks, Hkv, bs, hd] (kv heads gathered across tp
        shards).  One compile: callers pad the id list to
        ``max_blocks`` with the trash id and slice host-side, so the
        executable count never grows with prompt length — the
        disaggregation export primitive (serving/kv_transfer.py)."""
        if self._xfer_gather_fn is None:
            def body(pools, bids):
                return [
                    {"k": lp["k"][bids], "v": lp["v"][bids]}
                    for lp in pools
                ]

            self._xfer_gather_fn = jax.jit(
                jax.shard_map(
                    body, mesh=self.mesh,
                    in_specs=(self._cache_specs, P()),
                    out_specs=self._cache_specs,
                    check_vma=False,
                ),
            )
        return self._xfer_gather_fn

    def _scatter_blocks_jit(self):
        """Per-layer GLOBAL {k, v} arrays [max_blocks, Hkv, bs, hd] +
        [max_blocks] dst block ids → pools with those rows written.
        The inverse of ``_gather_blocks_jit``: the input's kv-head dim
        is split over the model axis by the in_spec, so a payload
        EXPORTED at one tp width imports at any other — the
        cross-layout ``model.load`` discipline applied to KV blocks.
        Padding rows carry the trash id, so their writes are dead by
        construction (same trick as decode's inactive slots)."""
        if self._xfer_scatter_fn is None:
            def body(pools, kv, bids):
                return [
                    {
                        "k": lp["k"].at[bids].set(lkv["k"]),
                        "v": lp["v"].at[bids].set(lkv["v"]),
                    }
                    for lp, lkv in zip(pools, kv)
                ]

            self._xfer_scatter_fn = jax.jit(
                jax.shard_map(
                    body, mesh=self.mesh,
                    in_specs=(self._cache_specs, self._cache_specs,
                              P()),
                    out_specs=self._cache_specs,
                    check_vma=False,
                ),
                donate_argnums=(0,),
            )
        return self._xfer_scatter_fn

    def export_blocks(self, block_ids) -> list[dict]:
        """Read ``block_ids``' K/V out of the pools as host numpy
        arrays (one {k, v} dict per layer, ``[n, Hkv, bs, hd]`` with
        the GLOBAL kv-head dim — tp-layout-free)."""
        bids = np.full((self.max_blocks,), self.trash_id, np.int32)
        n = len(block_ids)
        assert n <= self.max_blocks, (n, self.max_blocks)
        bids[:n] = np.asarray(block_ids, np.int32)
        gathered = self._gather_blocks_jit()(
            self.pools, jnp.asarray(bids)
        )
        return [
            {"k": np.asarray(lp["k"][:n]), "v": np.asarray(lp["v"][:n])}
            for lp in gathered
        ]

    def import_blocks(self, layers: list[dict], block_ids) -> None:
        """Write exported K/V rows into THIS decoder's pools at
        ``block_ids`` (freshly allocated by the caller).  Pads to the
        one compiled scatter shape; padding rows write to the trash
        block."""
        n = len(block_ids)
        assert n == len(layers[0]["k"]), (n, len(layers[0]["k"]))
        assert n <= self.max_blocks, (n, self.max_blocks)
        bids = np.full((self.max_blocks,), self.trash_id, np.int32)
        bids[:n] = np.asarray(block_ids, np.int32)
        m = self.model
        pad_shape = (self.max_blocks, m.n_kv_heads, self.block_size,
                     self._hd)
        padded = []
        for lkv in layers:
            k = np.zeros(pad_shape, np.asarray(lkv["k"]).dtype)
            v = np.zeros(pad_shape, np.asarray(lkv["v"]).dtype)
            k[:n] = lkv["k"]
            v[:n] = lkv["v"]
            padded.append({"k": jnp.asarray(k), "v": jnp.asarray(v)})
        self.pools = self._scatter_blocks_jit()(
            self.pools, padded, jnp.asarray(bids)
        )

    def bucket_for(self, prompt_len: int) -> int:
        """Servability check (same refusal contract as v1); paged
        prefill has ONE chunk shape, so the 'bucket' is always
        ``prefill_chunk``."""
        if not 1 <= prompt_len <= self.max_prefill:
            raise ValueError(
                f"prompt length {prompt_len} outside servable range "
                f"[1, {self.max_prefill}] (max_seq {self.max_seq} "
                f"leaves one position for generation)"
            )
        return self.prefill_chunk

    # -- host API ----------------------------------------------------------

    def copy_block(self, src: int, dst: int) -> None:
        """Device-side copy of one physical block (all layers, K and
        V) — the copy-on-write primitive ``BlockManager
        .ensure_writable`` calls.  One compile, scalar operands."""
        self.pools = self._copy_jit()(
            self.pools, jnp.int32(src), jnp.int32(dst)
        )

    def prefill(self, table_row, chunk_ids, start: int, q_len: int,
                key, temperature):
        """Run one prefill chunk; returns the sampled follow-on token
        as an UN-READ device array (meaningful on the final chunk —
        the caller's ``int()`` conversion is the TTFT fence, and
        skipping it on non-final chunks keeps a long prompt's chunk
        pipeline asynchronous).  ``chunk_ids`` may be shorter than
        ``prefill_chunk``; it is zero-padded to the fixed chunk
        shape."""
        assert 1 <= q_len <= self.prefill_chunk
        padded = np.zeros((self.prefill_chunk,), np.int32)
        padded[:q_len] = np.asarray(chunk_ids, np.int32)[:q_len]
        self.pools, tok = self._prefill_jit(temperature <= 0)(
            self.model.params, self.pools,
            jnp.asarray(table_row, jnp.int32),
            jnp.asarray(padded),
            jnp.int32(start), jnp.int32(q_len),
            jnp.asarray(key, jnp.uint32),
            jnp.float32(temperature),
        )
        return tok

    def decode(self, tokens, lengths, keys, temps, tables=None,
               active=None) -> np.ndarray:
        """One decode step for all slots through the block tables
        (host arrays in, host token ids [S] out)."""
        assert tables is not None and active is not None, (
            "paged decode needs the block tables and the active mask"
        )
        self.pools, nxt = self._decode_jit(
            bool(np.all(np.asarray(temps) <= 0.0))
        )(
            self.model.params, self.pools,
            jnp.asarray(tables, jnp.int32),
            jnp.asarray(tokens, jnp.int32),
            jnp.asarray(lengths, jnp.int32),
            jnp.asarray(keys, jnp.uint32),
            jnp.asarray(temps, jnp.float32),
            jnp.asarray(active, bool),
        )
        return np.asarray(nxt)

    def verify(self, tokens, lengths, keys, temps, tables,
               n_valid) -> np.ndarray:
        """One speculative verify step for all slots: ``tokens``
        [S, K] (column 0 committed, rest drafts), ``n_valid`` [S]
        (0 = inactive slot).  Host arrays in, host token matrix
        [S, K] out — the single ``np.asarray`` read is the step's
        device fence, same discipline as ``decode``.  The engine owns
        accept/reject; this is pure device math."""
        tokens = np.asarray(tokens, np.int32)
        self.pools, nxt = self._verify_jit(
            tokens.shape[1], bool(np.all(np.asarray(temps) <= 0.0))
        )(
            self.model.params, self.pools,
            jnp.asarray(tables, jnp.int32),
            jnp.asarray(tokens),
            jnp.asarray(lengths, jnp.int32),
            jnp.asarray(keys, jnp.uint32),
            jnp.asarray(temps, jnp.float32),
            jnp.asarray(n_valid, jnp.int32),
        )
        return np.asarray(nxt)

    # -- accounting --------------------------------------------------------

    def kv_cache_bytes(self) -> int:
        """Total HBM the block pools occupy (trash block included)."""
        return self.kv_bytes_per_block() * (
            self.manager.allocator.n_blocks + 1
        )

    def kv_bytes_per_block(self) -> int:
        m = self.model
        itemsize = jnp.dtype(self._cdtype).itemsize
        return (
            2 * m.n_layers * m.n_kv_heads * self.block_size
            * self._hd * itemsize
        )

    def kv_bytes_per_slot(self) -> int:
        """HBM per admitted request at FULL table occupancy — the
        worst case; the measured per-request figure is
        ``kv_bytes_per_block() * blocks_owned`` (the bench reports
        both)."""
        return self.kv_bytes_per_block() * self.max_blocks

    def _dummy_decode_args(self) -> tuple:
        s = self.max_slots
        return (
            self.model.params, self.pools,
            jnp.zeros((s, self.max_blocks), jnp.int32),
            jnp.zeros((s,), jnp.int32), jnp.zeros((s,), jnp.int32),
            jnp.zeros((s, 2), jnp.uint32), jnp.zeros((s,), jnp.float32),
            jnp.zeros((s,), bool),
        )

    def non_decode_hlo_texts(self, greedy: bool = True) -> list[str]:
        """Optimized HLO of the OTHER device executables a paged
        serving run dispatches (the prefill chunk and the CoW block
        copy) — subtract their ``trace_comm.hlo_instruction_names``
        from a decode marker set before attributing a trace that
        interleaves them: instruction names are unique per module
        only, and e.g. the prefill module's ``fusion.1`` would match
        a decode instruction of the same name."""
        from theanompi_tpu.utils.trace_comm import compiled_hlo_text

        pf = self._prefill_jit(greedy).lower(
            self.model.params, self.pools,
            jnp.zeros((self.max_blocks,), jnp.int32),
            jnp.zeros((self.prefill_chunk,), jnp.int32),
            jnp.int32(0), jnp.int32(1),
            jnp.zeros((2,), jnp.uint32), jnp.float32(0.0),
        )
        cp = self._copy_jit().lower(
            self.pools, jnp.int32(0), jnp.int32(0)
        )
        return [
            compiled_hlo_text(pf.compile()),
            compiled_hlo_text(cp.compile()),
        ]


def decoder_from_checkpoint(
    config: dict,
    directory: str,
    *,
    mesh=None,
    devices=None,
    paged: bool = False,
    **decoder_kw,
) -> LlamaDecoder:
    """The train → checkpoint → serve path in one call: build a
    ``Llama`` for the SERVING layout (``config['tp']`` etc.), restore
    weights through ``model.load`` — including sharded checkpoints
    and the validated/quarantine fallback path — and wrap it in a
    decoder (``paged=True`` → :class:`PagedLlamaDecoder`).  The
    checkpoint may come from any training layout; npz and sharded
    formats both reload across layouts.

    ``devices`` (or ``mesh``) says where the replica lives.  It may
    be left out only when the process sees exactly the ``tp`` devices
    the replica needs: "the first ``tp`` devices" as a default would
    stack every replica of a fleet on chip 0."""
    model = Llama(config)
    if mesh is None:
        if devices is None:
            devices = default_devices()
            if len(devices) > model.tp:
                raise ValueError(
                    f"decoder_from_checkpoint: {len(devices)} devices "
                    f"are visible and tp={model.tp} — pass devices= "
                    f"(or mesh=) to say which of them this replica "
                    f"uses"
                )
        mesh = make_mesh(data=1, model=model.tp, devices=devices)
    model.build_model(n_replicas=dp_replicas(mesh))
    model.compile_iter_fns(mesh=mesh)
    if not model.load(directory):
        raise FileNotFoundError(
            f"no loadable checkpoint under {directory!r}"
        )
    cls = PagedLlamaDecoder if paged else LlamaDecoder
    return cls(model, **decoder_kw)
