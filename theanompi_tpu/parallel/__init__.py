"""Parallelism layer: device meshes, exchange rules, and wire strategies.

TPU-native replacement for the reference's comm stack
(``theanompi/lib/exchanger.py`` + ``exchanger_strategy.py`` +
mpi4py/NCCL): collectives are emitted by XLA over ICI from
``shard_map``-ed pure functions, rather than called explicitly on
parameter buffers between train steps.
"""

from theanompi_tpu.parallel.mesh import (
    make_mesh,
    data_axis,
    dp_replicas,
    default_devices,
    DATA_AXIS,
    MODEL_AXIS,
    SEQ_AXIS,
    PIPE_AXIS,
    EXPERT_AXIS,
    num_devices,
)
from theanompi_tpu.parallel.pp import (
    pipeline_apply,
    last_stage_value,
    split_microbatches,
    merge_microbatches,
)
from theanompi_tpu.parallel.exchange import (
    FlatSpec,
    WIRE_COMPRESSIONS,
    allreduce_mean,
    compressed_allreduce_mean,
    dequantize_chunks,
    flat_pack,
    flat_pack_bucket,
    flat_spec,
    flat_spec_cache_clear,
    flat_spec_cache_info,
    flat_unpack,
    quantize_chunks,
    scatter_update_gather,
    elastic_pair_update,
    elastic_center_merge,
    elastic_center_merge_masked,
    gossip_push,
    gossip_merge,
    gossip_matrix_round,
    replica_consistency_delta,
)
from theanompi_tpu.parallel.moe import (
    aux_moments,
    load_balance_loss,
    moe_capacity,
    moe_ffn,
    router_topk,
)
from theanompi_tpu.parallel.plan import ExchangePlan
from theanompi_tpu.parallel.strategies import (
    COMPRESSION_CHOICES,
    DEFAULT_BUCKET_MB,
    ExchangeStrategy,
    get_strategy,
    resolve_bucket_mb,
    resolve_compression,
    STRATEGIES,
)

__all__ = [
    "make_mesh",
    "data_axis",
    "dp_replicas",
    "default_devices",
    "DATA_AXIS",
    "MODEL_AXIS",
    "SEQ_AXIS",
    "PIPE_AXIS",
    "EXPERT_AXIS",
    "num_devices",
    "pipeline_apply",
    "last_stage_value",
    "split_microbatches",
    "merge_microbatches",
    "FlatSpec",
    "WIRE_COMPRESSIONS",
    "allreduce_mean",
    "compressed_allreduce_mean",
    "dequantize_chunks",
    "flat_pack",
    "flat_pack_bucket",
    "flat_spec",
    "flat_spec_cache_clear",
    "flat_spec_cache_info",
    "flat_unpack",
    "quantize_chunks",
    "scatter_update_gather",
    "elastic_pair_update",
    "elastic_center_merge",
    "elastic_center_merge_masked",
    "gossip_push",
    "gossip_merge",
    "gossip_matrix_round",
    "replica_consistency_delta",
    "COMPRESSION_CHOICES",
    "DEFAULT_BUCKET_MB",
    "ExchangePlan",
    "ExchangeStrategy",
    "get_strategy",
    "resolve_bucket_mb",
    "resolve_compression",
    "STRATEGIES",
    "aux_moments",
    "load_balance_loss",
    "moe_capacity",
    "moe_ffn",
    "router_topk",
]
