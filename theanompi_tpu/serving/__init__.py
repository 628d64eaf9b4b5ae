"""Continuous-batching inference serving (the roadmap's "serve heavy
traffic" workload): KV-cache decode for Llama + a slot-based engine.

- ``decoder`` — model layer: tp-sharded GQA KV cache (slot-contiguous
  v1 ``LlamaDecoder``, or the v2 ``PagedLlamaDecoder``: block-table
  attention over fixed-size KV blocks, fixed-shape chunked prefill),
  layout-invariant greedy/temperature samplers (``parallel/tp.py``).
- ``blocks`` — host-side paged-cache accounting: refcounted block
  allocator, per-slot block tables, the copy-on-write gate.
- ``prefix_cache`` — radix/trie prefix cache keyed on token ids: a
  shared system prompt is prefilled once and ADOPTED by later
  requests (refcount bump + CoW on first divergent write).
- ``engine`` — Orca-style continuous batcher behind a thread-safe
  ``Engine.submit()`` front-end with admission control (queue cap +
  per-request deadlines + out-of-blocks accounting → load-shed
  results, never hangs) and chunked prefill interleaved with decode.
- ``replica`` — fleet unit: one engine behind a health-stamped owner
  loop, in-process (``InProcessReplica``) or in another process over
  the center-server TCP frames (``ReplicaServer`` /
  ``TCPReplicaClient``).
- ``router`` — fleet front-end: ``Router`` spreads requests over N
  replicas (round-robin / least-loaded / prefix-affinity consistent
  hashing), watches heartbeats supervisor-style, requeues a failed
  replica's queued AND in-flight requests to healthy members (every
  future still resolves), and aggregates telemetry through
  ``utils.recorder.FleetRecorder``.
- ``kv_transfer`` — disaggregated prefill/decode (v4): the portable
  KV handoff record a prefill-specialist replica ships to a
  decode-specialist (tp-layout-free; ``BlockManager`` tables are the
  receive substrate), with role-aware dispatch in the router and a
  unified fallback when no specialist is healthy.
- ``autoscaler`` — the control plane (v4): a supervisor-style policy
  loop that watches router backpressure against the fleet's slot
  capacity and spawns/retires replicas with hysteresis; scale-down
  drains through the failover path (never drops a request), and
  spawn/retire events feed ``FleetRecorder.replica_seconds`` — the
  cost metric of an autoscaled fleet (``tests/test_autoscaler.py``).

See docs/SERVING.md for lifecycle, knobs and telemetry.
"""

from theanompi_tpu.serving.autoscaler import Autoscaler

from theanompi_tpu.serving.blocks import (
    BlockAllocator,
    BlockManager,
    OutOfBlocks,
)
from theanompi_tpu.serving.decoder import (
    LlamaDecoder,
    PagedLlamaDecoder,
    decoder_from_checkpoint,
    default_prefill_buckets,
)
from theanompi_tpu.serving.engine import (
    Engine,
    Request,
    Result,
    ServingFuture,
)
from theanompi_tpu.serving.kv_transfer import (
    build_handoff,
    handoff_bytes,
    inject_handoff,
)
# NOTE: serving.paged_attention (the fused Pallas kernel) is NOT
# re-exported here — the decoder imports it lazily so fleet/router
# code that never selects paged_attend_impl="pallas" keeps
# jax.experimental.pallas off its import path; import
# `theanompi_tpu.serving.paged_attention.paged_attend` directly.
from theanompi_tpu.serving.prefix_cache import PrefixCache
from theanompi_tpu.serving.speculation import NGramDrafter
from theanompi_tpu.serving.tokenize import (
    ByteTokenizer,
    TokenizeService,
)
from theanompi_tpu.serving.replica import (
    InProcessReplica,
    ReplicaServer,
    TCPReplicaClient,
)
from theanompi_tpu.serving.router import (
    POLICIES,
    ConsistentHashRing,
    Router,
    prefix_affinity_key,
)

__all__ = [
    "Autoscaler",
    "BlockAllocator",
    "BlockManager",
    "ByteTokenizer",
    "ConsistentHashRing",
    "Engine",
    "InProcessReplica",
    "LlamaDecoder",
    "NGramDrafter",
    "OutOfBlocks",
    "POLICIES",
    "PagedLlamaDecoder",
    "PrefixCache",
    "ReplicaServer",
    "Request",
    "Result",
    "Router",
    "ServingFuture",
    "TCPReplicaClient",
    "TokenizeService",
    "build_handoff",
    "decoder_from_checkpoint",
    "default_prefill_buckets",
    "handoff_bytes",
    "inject_handoff",
    "prefix_affinity_key",
]
