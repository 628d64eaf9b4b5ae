"""Per-device model replicas for the async rules (EASGD, GoSGD).

The reference runs the async rules as independent OS processes, each
with its own full model copy, exchanging parameter buffers over MPI
(reference: ``theanompi/easgd_worker.py``, ``gosgd_worker.py``,
``theanompi/lib/exchanger.py``).  The TPU-native shape keeps ONE
controller but gives every device its *own* parameter/optimizer state:
all per-worker pytrees carry a leading worker axis ``W`` (== size of
the mesh's data axis) sharded across devices, and the local SGD step is
``jit(vmap(step))`` — no collectives inside, so each device advances
its replica independently and a "worker" is a mesh coordinate instead
of an MPI rank.

Exchanges (elastic with a replicated center, or gossip between slots)
are separate host-dispatched jitted calls — the honest analogue of the
reference's out-of-step MPI exchanges, and the one place the recorder's
``comm`` segment is a real wall-clock number (SURVEY §5.1).
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from theanompi_tpu.ops.layers import accuracy, softmax_cross_entropy
from theanompi_tpu.parallel import DATA_AXIS

PyTree = Any


def broadcast_stack(tree: PyTree, n: int, sharding=None) -> PyTree:
    """Tile every leaf with a new leading worker axis of size ``n``.

    Goes through a zero-copy host broadcast view so only each device's
    shard is ever materialized — ``jnp.broadcast_to`` on device would
    transiently allocate all ``n`` copies on the source device first.
    """

    def one(x):
        view = np.broadcast_to(np.asarray(x), (n,) + x.shape)
        if sharding is not None:
            return jax.device_put(view, sharding)
        return jnp.asarray(view)

    return jax.tree.map(one, tree)


def stacked_mean(tree: PyTree, weights: jnp.ndarray | None = None) -> PyTree:
    """Collapse the leading worker axis by (weighted) mean."""

    def one(x):
        f32 = x.astype(jnp.float32)
        if weights is None:
            m = jnp.mean(f32, axis=0)
        else:
            w = weights.astype(jnp.float32)
            w = w / jnp.sum(w)
            m = jnp.tensordot(w, f32, axes=[[0], [0]])
        return m.astype(x.dtype)

    return jax.tree.map(one, tree)


class ReplicaEngine:
    """W independent replicas of a built ``ClassifierModel``, one per
    data-axis device, advanced by a vmapped local train step.

    ``model`` must have run ``build_model`` (net, data, params exist).
    The engine leaves the model's own BSP compile path untouched; use
    ``model.compile_iter_fns(mesh=...)`` separately if the worker also
    needs the model's validation step.
    """

    def __init__(self, model, mesh: Mesh):
        self.model = model
        self.mesh = mesh
        self.n_workers = mesh.shape[DATA_AXIS]

        self.stacked_sharding = NamedSharding(mesh, P(DATA_AXIS))
        self.replicated = NamedSharding(mesh, P())
        # data arrives [W, B, ...]; shard the worker axis
        self.batch_sharding = NamedSharding(mesh, P(DATA_AXIS))

        if model.params is None:
            model._init_params()
        self.params = broadcast_stack(
            model.params, self.n_workers, self.stacked_sharding
        )
        self.net_state = broadcast_stack(
            model.net_state, self.n_workers, self.stacked_sharding
        )
        # a model compiled with a zero1 strategy (model.exchange, set
        # by compile_iter_fns) holds a ZeRO-sharded FLAT optimizer buffer
        # (1/N of the state per data-axis device) — the wrong shape
        # for the async rules, where every replica advances
        # independently and owns its whole state.  ONLY then re-init
        # full-shape state; otherwise stack model.opt_state as-is (a
        # resumed EASGD/GoSGD run restores the checkpointed consensus
        # momentum into it — re-initing unconditionally would
        # silently train from cold momentum).
        exchange = getattr(model, "exchange", None)
        opt_src = (
            model.optimizer.init(model.params)
            if exchange is not None and exchange.zero1
            else model.opt_state
        )
        self.opt_state = broadcast_stack(
            opt_src, self.n_workers, self.stacked_sharding
        )

        net = model.net
        optimizer = model.optimizer

        def local_step(params, net_state, opt_state, x, y, lr, rng):
            def loss_fn(p, s):
                out, new_s = net.apply(
                    p, s, model.prep_input(x), train=True, rng=rng
                )
                loss = model.compute_loss(out, y)
                err = 1.0 - accuracy(model.primary_logits(out), y)
                return loss, (new_s, err)

            (loss, (new_state, err)), grads = jax.value_and_grad(
                loss_fn, has_aux=True
            )(params, net_state)
            params, opt_state = optimizer.update(params, grads, opt_state, lr)
            return params, new_state, opt_state, loss, err

        # vmap over the worker axis; lr replicated, rng per worker.
        self._train_step = jax.jit(
            jax.vmap(local_step, in_axes=(0, 0, 0, 0, 0, None, 0)),
            donate_argnums=(0, 1, 2),
        )

        def masked_step(params, net_state, opt_state, x, y, lr, rng, m):
            """One local step, applied only where ``m`` (per-worker
            {0,1}) is set — the heterogeneous-speed machinery for the
            async rules: unmasked workers' state is untouched, so
            replicas genuinely advance by different step counts."""
            new_p, new_s, new_o, loss, err = local_step(
                params, net_state, opt_state, x, y, lr, rng
            )
            on = m > 0

            def keep(new, old):
                return jnp.where(on, new, old)

            return (
                jax.tree.map(keep, new_p, params),
                jax.tree.map(keep, new_s, net_state),
                jax.tree.map(keep, new_o, opt_state),
                loss,
                err,
            )

        self._train_step_masked = jax.jit(
            jax.vmap(masked_step, in_axes=(0, 0, 0, 0, 0, None, 0, 0)),
            donate_argnums=(0, 1, 2),
        )

        def local_val(params, net_state, x, y):
            out, _ = net.apply(
                params, net_state, model.prep_input(x), train=False
            )
            logits = model.primary_logits(out)
            loss = softmax_cross_entropy(logits, y)
            err = 1.0 - accuracy(logits, y)
            err5 = 1.0 - accuracy(logits, y, k=5)
            return loss, err, err5

        self._val_step = jax.jit(jax.vmap(local_val, in_axes=(0, 0, 0, 0)))
        # same weights on every device (e.g. the EASGD center / gossip
        # consensus) — no stacked broadcast needed
        self._val_step_shared = jax.jit(
            jax.vmap(local_val, in_axes=(None, None, 0, 0))
        )

        self._rng = jax.random.PRNGKey(model.seed + 17)

        from theanompi_tpu.data import HostStager

        self._stager = HostStager(self.batch_sharding)

    # -- batches ---------------------------------------------------------

    def put_batch(self, batch):
        """Reshape a flat global batch [W*B, ...] to [W, B, ...] and
        shard the worker axis (each device feeds its own replica).
        The transfer itself rides the shared ``data.HostStager``
        discipline — async puts, device ops labelled ``host_load`` —
        so the in-process async loops' feed profiles like the BSP
        model's and drops into a ``StreamingLoader`` as its stage."""
        x, y = batch
        w = self.n_workers
        x = np.asarray(x).reshape((w, -1) + tuple(x.shape[1:]))
        y = np.asarray(y).reshape((w, -1) + tuple(y.shape[1:]))
        return self._stager.stage((x, y))

    # -- stepping --------------------------------------------------------

    def train_step(self, batch, lr: float, step_mask=None):
        """One local SGD step on every replica; returns mean (loss, err)
        as device arrays (read them to fence).

        ``step_mask`` — optional ``[W]`` {0,1} array: only masked
        workers advance (heterogeneous speeds for the async rules);
        the mean is over the active workers."""
        return self.train_step_staged(
            self.put_batch(batch), lr, step_mask
        )

    def train_step_staged(self, staged, lr: float, step_mask=None):
        """``train_step`` on an ALREADY-staged ``[W, B, ...]`` device
        batch (from ``put_batch``) — for loops that keep batches
        device-resident (benches; pod loops reusing an HBM cache),
        where the per-step host transfer would dominate or distort
        the measurement."""
        x, y = staged
        self._rng, k = jax.random.split(self._rng)
        keys = jax.random.split(k, self.n_workers)
        if step_mask is None:
            (
                self.params,
                self.net_state,
                self.opt_state,
                losses,
                errs,
            ) = self._train_step(
                self.params,
                self.net_state,
                self.opt_state,
                x,
                y,
                jnp.float32(lr),
                keys,
            )
            return jnp.mean(losses), jnp.mean(errs)
        m = jnp.asarray(step_mask, jnp.float32)
        (
            self.params,
            self.net_state,
            self.opt_state,
            losses,
            errs,
        ) = self._train_step_masked(
            self.params,
            self.net_state,
            self.opt_state,
            x,
            y,
            jnp.float32(lr),
            keys,
            m,
        )
        n_on = jnp.maximum(jnp.sum(m), 1.0)
        return jnp.sum(losses * m) / n_on, jnp.sum(errs * m) / n_on

    def val_step(self, batch, params=None, net_state=None):
        """Validate; by default each replica scores its own batch shard
        and results are averaged.  Pass *unstacked* ``params`` /
        ``net_state`` (e.g. the EASGD center or gossip consensus) to
        score those shared weights on every shard instead."""
        x, y = self.put_batch(batch)
        if params is None and net_state is None:
            loss, err, err5 = self._val_step(
                self.params, self.net_state, x, y
            )
        else:
            p = self.model.params if params is None else params
            s = stacked_mean(self.net_state) if net_state is None else net_state
            loss, err, err5 = self._val_step_shared(p, s, x, y)
        return (
            float(jnp.mean(loss)),
            float(jnp.mean(err)),
            float(jnp.mean(err5)),
        )

    def validate(self, data, params=None, net_state=None):
        """Full validation sweep; returns mean ``(loss, err, err5)``
        over ``data.n_batch_val`` batches (the epoch-end loop both
        async workers share)."""
        tot = np.zeros(3)
        for j in range(data.n_batch_val):
            tot += self.val_step(
                data.val_batch(j), params=params, net_state=net_state
            )
        tot /= max(data.n_batch_val, 1)
        return tuple(tot)

    # -- consensus -------------------------------------------------------

    def mean_params(self, weights=None) -> PyTree:
        return stacked_mean(self.params, weights)

    def mean_net_state(self, weights=None) -> PyTree:
        return stacked_mean(self.net_state, weights)

    def mean_opt_state(self, weights=None) -> PyTree:
        return stacked_mean(self.opt_state, weights)
