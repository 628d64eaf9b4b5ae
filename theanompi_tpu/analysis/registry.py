"""Seed configuration for the tmcheck rule families.

The registry encodes what the serving/control-plane code already
practices, so the checkers enforce the existing discipline rather
than invent one:

- :data:`GUARDED_BY` — per-class lock attribute + the attributes that
  must only be touched with it held (rule TM101).  Seeded for the
  threaded control-plane classes; ``# guarded-by: _lock`` comments on
  ``self.attr = ...`` lines in ``__init__`` extend it per file.
  Attributes owned by a single thread by construction (the engine's
  slot mirrors, a replica's heartbeat dict) are deliberately NOT
  registered: the rule checks the lock discipline the code claims,
  not a fantasy one.
- :data:`HOT_EXACT` / :data:`HOT_SUBSTR` — function-name seeds for
  the JAX hot-path sanitizer (TM104/TM105): the decode/prefill/step
  loops where one host-sync per call is the contract and a
  per-iteration fence is the PR 6 regression class.  ``# tmcheck:
  hot`` on a def line opts any other function in; ``test_``-prefixed
  functions are exempt (tests fence deliberately to assert values).
- :data:`TRACED_WRAPPERS` — call names whose function-valued
  arguments become traced bodies (TM106's scope): inside these,
  wall-clock and host-RNG calls burn into the compiled artifact.
- :data:`DENY_UNDER_LOCK` — the TM103 deny list, documented in
  docs/ANALYSIS.md.
- :data:`PROFILE_SCOPES` / :data:`PROFILE_SCOPE_PREFIXES` — the
  ``jax.named_scope`` labels the step-phase profiler
  (``obs/profiler.py``) attributes trace time to, each mapped to its
  leg name.  Rule TM107 (``scopes.py``): every ``jax.named_scope``
  label in the tree must resolve here — an unregistered scope's ops
  silently fall into the profiler's "compute (unscoped)" leg, so the
  label would LOOK instrumented while measuring nothing.
"""

from __future__ import annotations

#: class name -> (lock attribute, attributes guarded by it).
GUARDED_BY: dict[str, tuple[str | None, frozenset]] = {
    # the fleet router: membership, pending table, dispatch queue and
    # cursor all mutate under the RLock from submit/watchdog/replica
    # callback threads
    "Router": ("_lock", frozenset({
        "_members", "_pending", "_queue", "_rr", "_ring", "_stopping",
    })),
    # the engine: the submit queue is the ONE cross-thread structure
    # (slots/mirrors are engine-loop-owned by construction)
    "Engine": ("_lock", frozenset({"_queue"})),
    # the TCP client: futures + command-reply slots are shared by the
    # submitting thread, the reader thread, and the pinger
    "TCPReplicaClient": ("_lock", frozenset({"_futures", "_replies"})),
    # single-owner loops: no lock-guarded state today; registered so
    # adding guarded state later starts from an explicit entry
    "InProcessReplica": (None, frozenset()),
    "Autoscaler": (None, frozenset()),
    "Supervisor": (None, frozenset()),
}

#: hot-path seeds: exact function names …  The tracer API
#: (obs/tracer.py span/start_span/end_span/record_span) is seeded
#: because spans are recorded INSIDE the decode/prefill loops: their
#: bodies must stay host-pure, and a device value fenced into a span
#: attribute at a call site in a hot function is the same
#: per-iteration round trip TM104 exists for (fixture-tested).
#: ``phase`` is the training path's span call
#: (``utils/recorder.Recorder.phase``): its attributes are host
#: values for the same reason, around every dispatch of the step.
#: The streaming loader's consumer/producer pair (data/pipeline.py
#: ``next``/``_produce``) is seeded because the pipeline only
#: overlaps if NEITHER side ever fences: one ``block_until_ready`` or
#: ``.item()`` in the producer serializes every staged transfer
#: behind a host round trip — exactly the per-batch host fence the
#: TM104 fixture pins (the PR 6 per-chunk ``int()`` lesson, applied
#: to data).  ``next`` also covers ``NativeBatchLoader.next``
#: (native/__init__.py), whose body is host-pure by construction.
HOT_EXACT = frozenset({
    "step", "decode", "decode_step", "prefill", "verify", "draft",
    "span", "start_span", "end_span", "record_span", "phase",
    "next", "_produce",
})
#: … and substrings (catches `_advance_prefill_slot`,
#: `_prepare_decode_writes`, `_spec_decode_once`, `_verify_body` and
#: their future siblings — "verify"/"draft" cover the speculative
#: path, where a per-draft-token host fence inside the verify loop
#: is the PR 6 per-chunk-fence bug class one level deeper)
HOT_SUBSTR = ("prefill", "decode", "verify", "draft")

#: call names whose callable arguments are traced (jitted/scanned)
TRACED_WRAPPERS = frozenset({
    "jit", "scan", "fori_loop", "while_loop", "cond", "pmap", "vmap",
    "grad", "value_and_grad", "checkpoint", "remat", "shard_map",
    "custom_vjp", "custom_jvp",
})

#: TM103: operations that must not run while holding a lock.  Keys
#: are symbolic op ids (used in messages); values document the match.
DENY_UNDER_LOCK = {
    "future-resolve": "`._set(...)` resolves a future: its done-"
                      "callbacks run on THIS thread, under the lock",
    "done-callback": "`.add_done_callback(...)` fires inline when the "
                     "future already resolved",
    "unbounded-send": "`send_frame(...)`/`.sendall(...)` without "
                      "timeout_s: a peer that stops reading wedges "
                      "the lock holder forever",
    "blocking-wait": "blocking `.result()`/queue `.get()`/thread "
                     "`.join()` parks the lock holder",
    "sleep": "`time.sleep(...)` holds the lock across a stall",
    "trace-export": "`chrome_trace(...)`/`critical_path(...)`/"
                    "`collect_spans(...)` serializes/pulls a whole "
                    "span ring (possibly over the wire) while "
                    "holding a lock",
}

#: profiler-scope registry (rule TM107; consumed by
#: ``obs/profiler.py``).  Exact ``jax.named_scope`` label -> the
#: StepProfile leg its ops are attributed to.  A label absent from
#: BOTH tables is TM107: the scope exists in the code but the
#: profiler would silently file its ops under "compute (unscoped)".
PROFILE_SCOPES: dict[str, str] = {
    # compressed-exchange codec halves (parallel/exchange.py, PR 4)
    "quantize_wire": "quantize",
    "dequantize_wire": "quantize",
    # optimizer update (parallel/plan.py ExchangePlan.apply,
    # scatter_update_gather's per-bucket/monolithic update)
    "opt_update": "optimizer",
    # serving decode attribution (serving/decoder.py, PR 6)
    "serving_sample": "sample",
    "paged_attend": "attend",
    "kv_write": "kv_write",
    # host→device batch staging (data/pipeline.py HostStager, PR 16):
    # the residual feed cost the streaming loader can't hide
    "host_load": "host_load",
    # the four parts of the expert layer (parallel/moe.py, PR 26);
    # benchmark/layer_metrics/_moe.py reads the same labels
    "moe_route": "moe_route",
    "moe_dispatch": "moe_dispatch",
    "moe_experts": "moe_experts",
    "moe_combine": "moe_combine",
    # the tile plan of the grouped-product kernels, built once a layer
    # call inside ``moe_experts`` (parallel/moe.py, PR 31)
    "moe_tile_plan": "moe_experts",
    # the dense MLP's activation gradient (ops/layers.py swiglu, PR 27)
    "mlp_act_grad": "mlp_act_grad",
    # a looped decoder's passes over its stack and its exits' heads
    # and losses (models/llama.py, PR 33);
    # benchmark/layer_metrics/_ut.py reads the same labels
    "ut_stack": "ut_stack",
    "ut_exit": "ut_exit",
    # latent attention's projections inside ``blk_attn``, the shared
    # expert inside ``blk_ffn`` and the multi-token-prediction module
    # with its own blocks inside (models/llama.py, parallel/moe.py,
    # PR 37); benchmark/layer_metrics/_scopes.py reads the same labels
    "mla_proj": "mla_proj",
    "moe_shared": "moe_shared",
    "mtp": "mtp",
    # a layer's attention under its kind's name, inside ``blk_attn``,
    # for a model described layer by layer (models/llama.py ``_gqa``,
    # PR 41); benchmark/layer_metrics/attn_sliding_ms.py reads the
    # window layers'
    "attn_sliding": "attn_sliding",
    "attn_full": "attn_full",
    # grouped-query attention between ``attn_norm`` and the flash
    # kernels, inside ``blk_attn`` and a kind's scope (models/llama.py
    # ``_gqa_qkv``, PR 45); benchmark/layer_metrics/gqa_proj_ms.py
    # reads the label
    "gqa_proj": "gqa_proj",
    # the sigmoid gate a query head between the attention kernels and
    # ``wo``, inside ``blk_attn`` and a kind's scope, outside
    # ``gqa_proj`` (models/llama.py ``_attn_gate``, PR 50);
    # benchmark/layer_metrics/attn_gate_ms.py reads the label
    "attn_gate": "attn_gate",
    # the two projections around routed experts that live in a latent,
    # inside ``blk_ffn``, outside the routed path's four scopes and
    # ``moe_shared`` (parallel/moe.py ``moe_ffn`` with ``latent``,
    # PR 55); benchmark/layer_metrics/moe_latent_ms.py reads the label
    "moe_latent": "moe_latent",
    # a state-space (mamba) layer's mixer under its block and the four
    # scopes inside it (models/llama.py ``_mamba_block``, ops/ssd.py
    # ``mamba_mixer``, PR 47); benchmark/layer_metrics/ssm_block_ms.py,
    # ssd_scan_ms.py, ssd_scan_roofline.py and ssm_conv_ms.py read
    # the labels
    "blk_ssm": "blk_ssm",
    "ssm_proj": "ssm_proj",
    "ssm_conv": "ssm_conv",
    "ssd_scan": "ssd_scan",
    "ssm_gate_norm": "ssm_gate_norm",
    # the step program's blocks (models/llama.py ``_forward`` /
    # ``_layer`` / ``loss_fn``, ops/layers.py, models/base.py, PR 35):
    # with ``opt_update`` and ``exchange_b<i>`` every instruction of a
    # step lies under one; the scopes above nest inside them.
    # benchmark/layer_metrics/_blocks.py reads any ``blk_`` label
    "blk_embed": "blk_embed",
    "blk_attn": "blk_attn",
    "blk_ffn": "blk_ffn",
    "blk_head": "blk_head",
    "blk_mtp_in": "blk_mtp_in",
    "blk_conv": "blk_conv",
    "blk_bn": "blk_bn",
    "blk_pool": "blk_pool",
}

#: label PREFIX -> leg family: labels carrying a per-instance index
#: (``exchange_b{i}`` — one leg per exchange bucket).  The profiler
#: keeps the full label as the leg name; TM107 accepts any literal
#: label (or f-string literal head) starting with a prefix.
PROFILE_SCOPE_PREFIXES: dict[str, str] = {
    "exchange_b": "exchange",
}

#: receiver-name hints -> class-name keywords, for resolving
#: `obj.method(...)` call sites to candidate classes in the
#: lock-order graph (TM102).  A hint that matches no analyzed class
#: falls back to "all classes defining the method".
RECEIVER_HINTS = {
    "engine": "engine",
    "replica": "replica",
    "router": "router",
    "client": "client",
    "fut": "future",
    "future": "future",
    "efut": "future",
    "recorder": "recorder",
    "decoder": "decoder",
    "dec": "decoder",
    "mgr": "manager",
    "manager": "manager",
    "allocator": "allocator",
    "cache": "cache",
    "supervisor": "supervisor",
    "autoscaler": "autoscaler",
}
