"""Device-mesh construction helpers.

The reference binds one OS process per GPU and wires them with MPI ranks
(reference: ``theanompi/lib/base.py`` — ``MPI_GPU_Process``: COMM_WORLD
setup + intra-node NCCL clique).  The TPU-native equivalent is a
`jax.sharding.Mesh` over all addressable devices: the "rank" becomes a
mesh coordinate, and the NCCL clique becomes the ICI fabric that XLA
collectives ride for free.

Axis conventions (used throughout the framework):

- ``data``  — data parallelism (the reference's only axis).
- ``model`` — tensor parallelism (new-framework scope; the reference's
  predecessor ``theano_alexnet`` had a 2-GPU model-parallel AlexNet).
- ``seq``   — sequence/context parallelism for ring attention
  (new-framework scope; Llama-3-8B stretch config).
- ``expert`` — expert parallelism for MoE layers (new-framework
  scope).  Batches shard over ``(expert, data)`` jointly — EP ranks
  are data-parallel replicas that additionally shard the expert
  weights and exchange routed tokens over an ``all_to_all`` — so a
  size-1 expert axis (the default) is exactly the classic mesh.
"""

from __future__ import annotations

import os
from typing import Sequence

import jax
import numpy as np
from jax.sharding import Mesh

DATA_AXIS = "data"
MODEL_AXIS = "model"
SEQ_AXIS = "seq"
PIPE_AXIS = "pipe"
EXPERT_AXIS = "expert"


def default_devices() -> list[jax.Device]:
    """Devices the framework builds meshes from: JAX's default
    backend's, or — with ``TM_TPU_PLATFORM`` set — that backend's
    (``cpu`` for a dry run on the virtual host mesh in a process whose
    default backend is the chip; ``__graft_entry__`` does this).  The
    answer is always a list of real devices: a platform JAX does not
    have raises."""
    plat = os.environ.get("TM_TPU_PLATFORM")
    return jax.devices(plat) if plat else jax.devices()


def num_devices() -> int:
    return len(default_devices())


def make_mesh(
    data: int | None = None,
    model: int = 1,
    seq: int = 1,
    pipe: int = 1,
    expert: int = 1,
    *,
    devices: Sequence[jax.Device] | None = None,
) -> Mesh:
    """Build a ``Mesh`` with ``(pipe, expert, data, model, seq)`` axes.

    ``data=None`` means "all remaining devices after
    pipe×expert×model×seq".  On a real slice the device order from
    ``jax.devices()`` already follows the physical torus, so
    contiguous reshaping keeps the ``model`` and ``seq`` axes on
    nearest-neighbour ICI links (these axes carry the
    latency-sensitive collectives: TP psums and ring-attention
    ppermutes), while ``data`` — bandwidth-bound but latency-tolerant
    allreduces — and ``expert`` — the MoE token ``all_to_all``,
    bandwidth-bound, once per MoE layer — span outer dimensions and
    ``pipe`` — one activation hop per pipeline tick, the least
    latency-sensitive traffic — spans the outermost (on a multi-host
    pod it may even cross DCN).
    """
    devs = list(devices) if devices is not None else default_devices()
    n = len(devs)
    if pipe * expert * model * seq > n:
        raise ValueError(
            f"pipe*expert*model*seq={pipe * expert * model * seq} "
            f"exceeds {n} devices"
        )
    if data is None:
        data = n // (pipe * expert * model * seq)
    want = pipe * expert * data * model * seq
    if want > n:
        raise ValueError(
            f"mesh {pipe}x{expert}x{data}x{model}x{seq}={want} "
            f"exceeds {n} devices"
        )
    grid = np.array(devs[:want]).reshape(pipe, expert, data, model, seq)
    return Mesh(
        grid, (PIPE_AXIS, EXPERT_AXIS, DATA_AXIS, MODEL_AXIS, SEQ_AXIS)
    )


def data_axis(mesh: Mesh) -> int:
    """Size of the data-parallel axis of ``mesh``."""
    return mesh.shape[DATA_AXIS]


def dp_replicas(mesh: Mesh) -> int:
    """Number of data-parallel replicas of ``mesh``: expert × data —
    EP ranks are DP replicas that additionally shard the expert
    weights (the one place the convention is defined)."""
    return mesh.shape.get(EXPERT_AXIS, 1) * mesh.shape[DATA_AXIS]
