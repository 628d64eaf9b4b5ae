"""Model contract + generic SPMD classifier base.

Reference contract (README-documented; SURVEY §1 L2): workers drive a
duck-typed model exposing ``build_model / compile_iter_fns /
train_iter / val_iter / adjust_hyperp / params / data / epoch /
n_epochs``.  ``ClassifierModel`` implements the contract generically
for image classifiers built on ``theanompi_tpu.ops``; concrete models
(wresnet, alex_net, ...) subclass it and provide the network + config.

The single biggest architectural difference from the reference
(SURVEY §3.4): the train step is ONE jitted SPMD function —
forward + backward + gradient allreduce + optimizer update — so the
exchanger is *inside* the step and XLA overlaps the allreduce with
backprop.  ``compile_iter_fns`` is the rebuild of the reference's
``theano.function`` compilation, with the mesh and exchange strategy
as arguments.
"""

from __future__ import annotations

import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from theanompi_tpu.obs.setup import setup_phase
from theanompi_tpu.ops import optimizers as opt_lib
from theanompi_tpu.ops.layers import accuracy, softmax_cross_entropy
from theanompi_tpu.parallel import (
    DATA_AXIS,
    ExchangePlan,
    allreduce_mean,
    make_mesh,
)
from theanompi_tpu.utils import (
    Recorder,
    is_sharded_checkpoint,
    latest_checkpoint,
    load_checkpoint,
    load_sharded_checkpoint,
    save_checkpoint,
    save_sharded_checkpoint,
)
from theanompi_tpu.utils.xla_options import xla_compiler_options

PyTree = Any


class TMModel:
    """Abstract contract; subclass or duck-type it.

    ``build_model(n_replicas=...)`` receives the data-parallel replica
    count so the model can size its data pipeline's global batch (the
    reference sized per-GPU batches implicitly, one process per GPU).
    """

    params: PyTree
    data: Any
    epoch: int
    n_epochs: int
    #: EF residual of a compressed exchange (empty when off); models
    #: that compile one overwrite this with device state
    ef_state: PyTree = {}
    #: how the gradients travel (``parallel.ExchangePlan``, bound to
    #: the compiled step's layout); set by compile_iter_fns
    exchange: ExchangePlan | None = None
    #: what ``load`` attached from the newest checkpoint; a compile
    #: AFTER a restore consults it through the plan
    _restored: dict = {}

    @property
    def exchange_replicas(self) -> int | None:
        """Size of the replica group the gradient exchange reduces
        over (None before the compile)."""
        return self.exchange and self.exchange.exchange_replicas

    @property
    def exchange_buckets(self) -> int | None:
        """``exchange_b*`` bodies the compiled step traced (0 when a
        group of one exchanges nothing)."""
        return self.exchange and self.exchange.exchange_buckets

    def _exchange_layouts(self) -> tuple:
        """``(zero1_layout, ef_layout)`` of the compiled exchange —
        the checkpoint stamps (None, None before a compile)."""
        plan = self.exchange
        if plan is None:
            return None, None
        return plan.zero1_layout, plan.ef_layout

    #: the devices' ``bytes_limit`` a model's keep rule read in
    #: compile_iter_fns (None: the runtime gave none, or no such rule)
    keep_bytes_limit: int | None = None

    def keep_account(self, bytes_limit: int | None) -> dict | None:
        """The rule's side of the run's memory account
        (``obs/memory.py``): what a model that sizes what its step
        keeps by the device's memory decided from and left
        (``Llama.keep_account``); None for a model without such a
        rule."""
        return None

    def build_model(self, n_replicas: int = 1) -> None:
        raise NotImplementedError

    def compile_iter_fns(self, **kwargs) -> None:
        raise NotImplementedError

    def train_iter(self, count: int, recorder: Recorder) -> None:
        raise NotImplementedError

    def val_iter(self, count: int, recorder: Recorder):
        raise NotImplementedError

    # -- device-resident multi-step dispatch (shared by the cached
    # classifier and Llama paths; subclasses build _train_scan) ----------

    _train_scan = None
    _scan_k = 0

    def preferred_chunk(self, remaining: int) -> int:
        """Steps ``train_chunk`` should take in one dispatch: the
        compiled scan length when the device-resident scan path is
        live and fits in ``remaining``, else 1."""
        if self._train_scan is not None and remaining >= self._scan_k:
            return self._scan_k
        return 1

    def train_chunk(self, count: int, k: int, recorder: Recorder) -> None:
        """Default: a per-step loop (scan-capable subclasses override
        or dispatch through their compiled multi-step executable)."""
        for j in range(k):
            self.train_iter(count + j, recorder)

    def _stage_cached_inputs(self) -> None:
        """Restage the epoch permutation / lr when they changed — the
        only host→device traffic on the device-resident path."""
        rep = NamedSharding(self.mesh, P())
        perm = self.data.epoch_permutation()
        if perm is not self._perm_src:
            self._perm_src = perm
            self._perm_dev = jax.device_put(
                jnp.asarray(perm, jnp.int32), rep
            )
        if self.current_lr != self._lr_val:
            self._lr_val = self.current_lr
            self._lr_dev = jax.device_put(
                jnp.float32(self.current_lr), rep
            )

    # -- schedules (reference: adjust_hyperp per model) -------------------

    def adjust_hyperp(self, epoch: int) -> None:
        """Shared lr-schedule knobs: dict {epoch: lr} or 'step' decay.
        No-op for duck-typed models without a ``config`` dict."""
        sched = getattr(self, "config", {}).get("lr_schedule")
        if isinstance(sched, dict) and epoch in sched:
            self.current_lr = float(sched[epoch])
        elif sched == "step":
            every = self.config.get("lr_step_every", 20)
            gamma = self.config.get("lr_step_gamma", 0.1)
            self.current_lr = self.config.get("lr", 0.1) * (
                gamma ** (epoch // every)
            )

    # -- checkpoint / resume (reference: helper_funcs save/load) ----------

    def checkpoint_trees(self) -> dict[str, PyTree]:
        """Named pytrees to checkpoint; group names must be attribute
        names on the model (restore assigns them back via setattr)."""
        raise NotImplementedError

    def _place_restored(self) -> None:
        """Hook: re-place restored (host) trees onto the mesh."""

    def _checkpoint_format(self, trees: dict[str, PyTree]) -> str:
        """'sharded' when any leaf is partitioned over devices (then a
        host gather of the full tree would defeat the sharded init —
        SURVEY §5.4), else the dependency-free single-file 'npz'.
        Overridable via config['checkpoint_format']."""
        fmt = getattr(self, "config", {}).get("checkpoint_format", "auto")
        if fmt != "auto":
            return fmt

        def partitioned(x):
            return (
                isinstance(x, jax.Array)
                and len(x.sharding.device_set) > 1
                and not x.sharding.is_fully_replicated
            )

        for tree in trees.values():
            if any(partitioned(l) for l in jax.tree.leaves(tree)):
                return "sharded"
        return "npz"

    def save(
        self,
        directory: str,
        recorder: Recorder | None = None,
        extra_meta: dict | None = None,
    ) -> None:
        """``extra_meta`` rides in the sidecar — the graceful-
        preemption path stamps ``next_iter`` so a mid-epoch checkpoint
        resumes at the exact boundary instead of redoing (or worse,
        skipping) the epoch.  ``config['keep_last_checkpoints']``
        bounds on-disk history for supervised many-restart runs."""
        meta = {"epoch": self.epoch, "lr": self.current_lr}
        # world stamp (elastic resume): the DP replica count and the
        # global batch this run trained at — the resharding loader
        # needs the shard count the flat layouts were written under,
        # and the worker's elastic_batch_policy needs the global batch
        # to hold it constant across a world change
        mesh = getattr(self, "mesh", None)
        if mesh is not None:
            from theanompi_tpu.parallel import dp_replicas

            meta["world_size"] = int(dp_replicas(mesh))
            meta["n_devices"] = int(mesh.devices.size)
        gb = getattr(getattr(self, "data", None), "global_batch", None)
        if gb is not None:
            meta["global_batch"] = int(gb)
        # stream cursor (elastic resume of the pipelined feed): epoch +
        # next SAMPLE offset identify the stream position exactly — the
        # permutation is derived state (shuffle(epoch) reseeds it), and
        # sample units survive an elastic global-batch regrid
        feed = getattr(self, "_feed", None)
        if feed is not None:
            meta["loader_cursor"] = dict(feed.cursor(), epoch=self.epoch)
        if recorder is not None:
            meta["recorder"] = recorder.state_dict()
        if extra_meta:
            meta.update(extra_meta)
        keep_last = getattr(self, "config", {}).get(
            "keep_last_checkpoints"
        )
        keep_last = int(keep_last) if keep_last is not None else None
        # zero1 optimizer shards are flat buffers whose INTERNAL order
        # depends on the bucket layout (bucket-major when bucketed) —
        # stamp it so a resume under a different exchange_bucket_mb
        # refuses instead of silently pairing m/v rows with the wrong
        # params (the shapes alone can coincide across layouts)
        z_layout, ef_layout = self._exchange_layouts()
        if z_layout is not None:
            meta["zero1_layout"] = list(z_layout)
        # the error-feedback residual of a compressed exchange is part
        # of worker state: a resume that silently dropped (or
        # re-zeroed) it would break the interrupted==uninterrupted
        # bitwise guarantee, so its layout is stamped like the zero1
        # bucket layout and checked on load
        if ef_layout is not None:
            meta["ef_layout"] = list(ef_layout)
        trees = self.checkpoint_trees()
        if self._checkpoint_format(trees) == "sharded":
            save_sharded_checkpoint(
                directory, self.epoch, trees, meta, keep_last=keep_last
            )
        else:
            save_checkpoint(
                directory, self.epoch, trees, meta, keep_last=keep_last
            )

    def _world_hint(self, path) -> tuple[dict, int | None, bool]:
        """``(meta, n_here, world_changed)`` of a checkpoint, read
        WITHOUT loading arrays.  ``world_changed`` is the one rule
        both the reshard plan and the refusal guards share: a
        (padded, bucket_len) stamp can COINCIDE across worlds (both
        round to multiples of n), but the bucket-major storage
        permutation is n-dependent and r1 residuals are per-device
        state — so the world stamp, not the layout stamp alone,
        decides."""
        from theanompi_tpu.utils.checkpoint import checkpoint_meta

        meta = checkpoint_meta(path)
        n_here = None
        if self.mesh is not None:
            from theanompi_tpu.parallel import dp_replicas

            n_here = int(dp_replicas(self.mesh))
        world_changed = (
            meta.get("world_size") is not None
            and n_here is not None
            and int(meta["world_size"]) != n_here
        )
        return meta, n_here, world_changed

    def _load_trees(self, path, like: dict) -> tuple[dict, dict]:
        """Format dispatch + the curated missing-EF diagnostic (both
        load paths — a raw KeyError for the residual group is a dead
        end either way)."""
        try:
            if is_sharded_checkpoint(path):
                return load_sharded_checkpoint(path, like)
            return load_checkpoint(path, like)
        except KeyError as e:
            # only translate when the MISSING leaf is the residual's
            # (both loaders name the group in the error) — any other
            # group's mismatch keeps its own diagnostic
            if "ef_state" in like and "ef_state" in str(e):
                raise ValueError(
                    f"checkpoint {path} lacks the error-feedback "
                    f"residual group ('ef_state') this model's "
                    f"compressed exchange carries — resuming would "
                    f"silently drop the EF residual and break the "
                    f"interrupted==uninterrupted guarantee; resume "
                    f"from a checkpoint written with the same "
                    f"exch_compression, or set "
                    f"exch_compression='none'"
                ) from e
            raise

    def _reshard_plan(self, meta: dict, n_new: int | None,
                      world_changed: bool, like: dict) -> dict | None:
        """Decide whether an elastic load must reshard the flat
        exchange layouts (zero1 optimizer shards, EF residuals).
        ``None`` = layouts already match (or no layout-sensitive
        state) — the normal loader runs."""
        cur_z, cur_ef = self._exchange_layouts()
        saved_z = meta.get("zero1_layout")
        saved_ef = meta.get("ef_layout")
        groups: dict[str, tuple] = {}
        if cur_z is not None and saved_z is not None and (
            tuple(saved_z) != tuple(cur_z)
            or (cur_z[1] and world_changed)
        ):
            groups["opt_state"] = (tuple(saved_z), tuple(cur_z))
        if cur_ef is not None and saved_ef is not None and "ef_state" in like:
            if saved_ef[0] != cur_ef[0]:
                raise ValueError(
                    f"elastic resume cannot reshard across wire "
                    f"formats: the checkpoint's EF residual was "
                    f"written under exch_compression="
                    f"{saved_ef[0]!r}, the compiled exchange uses "
                    f"{cur_ef[0]!r} — the layouts/padding may change "
                    f"across worlds, the compression must not"
                )
            # r1 is PER-DEVICE state: any world change reshards the
            # residual group, equal layout stamps or not
            if tuple(saved_ef) != tuple(cur_ef) or world_changed:
                groups["ef_state"] = (
                    (saved_ef[1], saved_ef[2]),
                    (cur_ef[1], cur_ef[2]),
                )
        if not groups:
            return None
        return {
            "groups": groups,
            "world_size": meta.get("world_size"),
            "n_new": n_new,
            "size": sum(
                math.prod(jnp.shape(l))
                for l in jax.tree.leaves(self.params)
            ),
        }

    def _load_resharded(
        self, path, like: dict, plan: dict
    ) -> tuple[dict, dict]:
        """The elastic load: layout-portable groups (params,
        net_state) restore through the normal cross-layout loaders;
        layout-SENSITIVE flat buffers are read raw at their saved
        shapes, gathered to master (pack) order, and re-scattered
        under the compiled layout (``utils/reshard.py``) — an exact
        permutation, so gathered optimizer state stays bitwise."""
        from theanompi_tpu.utils import reshard as _reshard
        from theanompi_tpu.utils.checkpoint import load_npz_group
        from theanompi_tpu.utils.sharded_checkpoint import (
            load_sharded_group,
        )

        groups = plan["groups"]
        direct = {g: t for g, t in like.items() if g not in groups}
        trees, meta = self._load_trees(path, direct)
        raw_load = (
            load_sharded_group if is_sharded_checkpoint(path)
            else load_npz_group
        )
        n_old, n_new = plan["world_size"], plan["n_new"]
        for group, (old, new) in groups.items():
            fn = (
                _reshard.reshard_ef_tree if group == "ef_state"
                else _reshard.reshard_flat_tree
            )
            trees[group] = fn(
                raw_load(path, group),
                like[group],
                size=plan["size"],
                old=(n_old, *old),
                new=(n_new, *new),
            )
        print(
            f"elastic resume: resharded {sorted(groups)} from world "
            f"{n_old} to world {n_new} "
            f"(gather to master order, re-scatter)",
            flush=True,
        )
        return trees, meta

    def load(
        self,
        directory: str,
        recorder: Recorder | None = None,
        reshard: bool | None = None,
    ) -> bool:
        """Restore the newest valid checkpoint.  ``reshard=True`` (or
        ``config["elastic"]`` truthy) enables the ELASTIC path: a
        checkpoint whose zero1/EF flat layouts were written under a
        different data-parallel width is gathered to master order and
        re-scattered onto the compiled layout instead of refusing —
        the resize-the-world resume (docs/RESILIENCE.md)."""
        if reshard is None:
            reshard = bool(getattr(self, "config", {}).get("elastic"))
        # validate by default: a post-commit bit flip must fall back
        # to the previous valid checkpoint (quarantining the corrupt
        # one), never load blindly.  config['validate_checkpoint']=False
        # opts out (e.g. enormous sharded trees on a trusted store).
        validate = bool(
            getattr(self, "config", {}).get("validate_checkpoint", True)
        )
        path = latest_checkpoint(directory, validate=validate)
        if path is None:
            return False
        like = self.checkpoint_trees()
        meta_hint, n_here, world_changed = self._world_hint(path)
        plan = (
            self._reshard_plan(meta_hint, n_here, world_changed, like)
            if reshard else None
        )
        if plan is not None:
            trees, meta = self._load_resharded(path, like, plan)
            return self._finish_load(
                trees, meta, recorder,
                resharded={
                    "world_size": plan["world_size"],
                    "groups": sorted(plan["groups"]),
                },
            )
        # bucket-layout guard BEFORE anything loads (the raw shape
        # mismatch a cross-world zero1 resume would otherwise die on
        # is a dead end; this one names the escape hatch): when this
        # model already compiled a zero1 step, the restored flat
        # optimizer shard is only meaningful under the layout it was
        # saved with (missing marker = a pre-bucketing monolithic
        # checkpoint), and — _world_hint's coinciding-stamp rule — a
        # bucketed layout under a DIFFERENT world is a mismatch even
        # when the stamps agree
        cur, cur_ef = self._exchange_layouts()
        if cur is not None and "opt_state" in like:
            saved = meta_hint.get("zero1_layout")
            saved = tuple(saved) if saved is not None else (cur[0], 0)
            if saved != tuple(cur) or (cur[1] and world_changed):
                raise ValueError(
                    f"zero1 optimizer checkpoint layout {saved} "
                    f"(padded, bucket_len) does not match the "
                    f"compiled exchange layout {tuple(cur)} — the "
                    f"flat shard order is bucket-dependent, so "
                    f"resuming would silently pair adam/momentum "
                    f"rows with the wrong parameters; set "
                    f"exchange_bucket_mb to the value the checkpoint "
                    f"was trained with, or pass reshard=True to "
                    f"load() / set config['elastic']=True to gather "
                    f"the shards to master order and re-scatter them "
                    f"onto this layout (elastic resume, "
                    f"docs/RESILIENCE.md)"
                )
        # EF-layout guard, same shape as the zero1 one: the residual's
        # flat order is (compression, padded, bucket_len)-dependent,
        # so a mismatched resume must refuse instead of re-injecting
        # rows against the wrong parameters
        if cur_ef is not None and "ef_state" in like:
            saved_ef = meta_hint.get("ef_layout")
            # a checkpoint with NO residual at all (saved_ef None)
            # falls through to the loader's missing-group diagnostic
            if saved_ef is not None and (
                tuple(saved_ef) != tuple(cur_ef) or world_changed
            ):
                raise ValueError(
                    f"checkpoint EF-residual layout "
                    f"{tuple(saved_ef)} (compression, "
                    f"padded, bucket_len) does not match the compiled "
                    f"exchange layout {tuple(cur_ef)} — set "
                    f"exch_compression/exchange_bucket_mb to the "
                    f"values the checkpoint was trained with, or "
                    f"pass reshard=True to load() / set "
                    f"config['elastic']=True to carry the residual "
                    f"across the layout change (elastic resume, "
                    f"docs/RESILIENCE.md; the compression itself "
                    f"must still match)"
                )
        trees, meta = self._load_trees(path, like)
        return self._finish_load(trees, meta, recorder)

    def _finish_load(
        self,
        trees: dict,
        meta: dict,
        recorder: Recorder | None,
        resharded: dict | None = None,
    ) -> bool:
        """Attach restored trees + metadata (shared by the normal and
        elastic-reshard load paths).  After a reshard the state lives
        in the COMPILED layout, so the restored-layout markers record
        the current stamps, not the checkpoint's."""
        if resharded is None:
            z_layout, ef_layout = (
                meta.get("zero1_layout"), meta.get("ef_layout")
            )
        else:
            z_layout, ef_layout = self._exchange_layouts()
        # a later compile_iter_fns must neither zero a restored
        # optimizer state or EF residual nor keep one in another
        # layout (compile-then-load is the supported order): the
        # plan's restore checks read this record
        self._restored = {
            "opt_state": "opt_state" in trees,
            "ef_state": "ef_state" in trees,
            "zero1_layout": z_layout,
            "ef_layout": ef_layout,
            # the checkpoint carries an EF residual (its layout is
            # stamped) that this load did NOT attach — the model had
            # not compiled its compressed exchange yet, so
            # checkpoint_trees() had no ef_state slot
            "ef_orphaned": (
                resharded is None
                and meta.get("ef_layout") is not None
                and "ef_state" not in trees
            ),
        }
        # workers read this for resilience metadata the load() bool
        # can't carry: next_iter (mid-epoch preemption checkpoints),
        # preempted flag, restored recorder history, the saved world
        self.restored_meta = meta
        self.resharded_from = resharded
        for group, tree in trees.items():
            setattr(self, group, tree)
        self.epoch = int(meta.get("epoch", 0))
        self.current_lr = float(meta.get("lr", self.current_lr))
        if recorder is not None and "recorder" in meta:
            recorder.load_state_dict(meta["recorder"])
        self._place_restored()
        return True

    # -- streaming feed (theanompi_tpu/data: the data plane) --------------

    def _init_feed(self, sharding, dtypes=None) -> None:
        """Build the host→device staging path for this compile: a
        :class:`~theanompi_tpu.data.HostStager` (the one copy of the
        transfer discipline — async ``device_put`` + ``host_load``
        scope label) always, plus a
        :class:`~theanompi_tpu.data.StreamingLoader` feed when the
        ``loader_pipeline`` knob asks for one and the model is not
        already on a device-resident batch path (the HBM dataset
        cache moves zero bytes per step — pipelining host transfers
        that don't happen would only burn a thread)."""
        from theanompi_tpu.data import (
            HostStager, StreamingLoader, resolve_loader_depth,
        )

        self.close_feed()
        self._stager = HostStager(sharding, dtypes=dtypes)
        depth = resolve_loader_depth(getattr(self, "config", {}))
        if not depth:
            return
        if (getattr(self, "_device_cache", None) is not None
                or getattr(self, "_train_scan", None) is not None):
            import warnings

            warnings.warn(
                "loader_pipeline requested alongside an active "
                "device_data_cache path; the HBM cache already moves "
                "zero bytes per step — streaming feed disabled",
                stacklevel=3,
            )
            return
        data = self.data
        self._feed = StreamingLoader(
            data.train_batch,
            self._stager.stage,
            n_batches=lambda: data.n_batch_train,
            depth=depth,
            global_batch=int(data.global_batch),
            sample_ids=getattr(data, "batch_indices", None),
            journal_meta=self._feed_meta,
        )

    def _feed_meta(self) -> dict:
        """Journal stamp for the loader's sample-id accounting: the
        epoch disambiguates permutation windows across an elastic
        relaunch; the device count records the world each delivery
        happened under (the drills' world-history evidence)."""
        meta = {"epoch": int(self.epoch)}
        mesh = getattr(self, "mesh", None)
        if mesh is not None:
            meta["world"] = int(mesh.devices.size)
        return meta

    def close_feed(self) -> None:
        """Stop the streaming feed's producer thread (run() exit;
        recompiles).  Idempotent; a no-op on the synchronous feed."""
        feed = getattr(self, "_feed", None)
        if feed is not None:
            feed.stop()
        self._feed = None

    def stage_hlo_text(self) -> str | None:
        """Optimized HLO of the staging executable — the aux text
        ``step_profile`` merges into scope attribution so the
        ``host_load`` leg prices the residual feed cost (the main
        step's module cannot contain the staging ops: ``device_put``
        is not a traced op).  None until a batch has been staged."""
        stager = getattr(self, "_stager", None)
        return stager.hlo_text() if stager is not None else None


class ClassifierModel(TMModel):
    """Generic SPMD image classifier satisfying the contract.

    Subclasses set (in ``__init__`` or ``build_model``):
    - ``self.net`` — a ``theanompi_tpu.ops.Layer`` ending in logits
    - ``self.input_shape`` — per-example shape, e.g. ``(32, 32, 3)``
    - ``self.data`` — data object (``n_batch_train``, ``n_batch_val``,
      ``train_batch(i)``, ``val_batch(i)``, optional ``shuffle(epoch)``)
    - ``self.optimizer`` — an ``ops.Optimizer`` (default momentum 0.9)

    Config knobs follow the reference's per-model dicts (SURVEY §5.6):
    ``batch_size`` (per replica), ``n_epochs``, ``lr``, ``lr_schedule``
    (dict epoch→lr or 'step'), ``weight_decay``, ``exch_strategy``.
    """

    def __init__(self, config: dict | None = None):
        self.config = dict(config or {})
        self.n_epochs: int = self.config.get("n_epochs", 10)
        self.epoch: int = 0
        self.current_lr: float = self.config.get("lr", 0.1)
        self.compute_dtype = jnp.dtype(
            self.config.get("compute_dtype", "bfloat16")
        )
        self.seed = int(self.config.get("seed", 42))

        self.net = None
        self.data = None
        self.input_shape: tuple = ()
        self.optimizer = opt_lib.momentum(
            mu=self.config.get("momentum", 0.9),
            weight_decay=self.config.get("weight_decay", 1e-4),
        )

        self.params: PyTree = None
        self.net_state: PyTree = None
        self.opt_state: PyTree = None
        self.ef_state: PyTree = {}
        self.mesh: Optional[Mesh] = None
        self._train_step = None
        self._val_step = None
        self._rng = jax.random.PRNGKey(self.seed)

    # -- construction ----------------------------------------------------

    def build_model(self, n_replicas: int = 1) -> None:
        """Define ``self.net``/``self.data`` and initialize params."""
        raise NotImplementedError

    def _init_params(self) -> None:
        key = jax.random.PRNGKey(self.seed)
        self.params, self.net_state, self._out_shape = self.net.init(
            key, self.input_shape
        )
        self.opt_state = self.optimizer.init(self.params)

    # -- compilation (reference: theano.function of fwd+bwd+update) -------

    def compile_iter_fns(
        self,
        mesh: Mesh | None = None,
        exch_strategy: str | None = None,
    ) -> None:
        if self.params is None:
            self._init_params()
        self.mesh = mesh if mesh is not None else make_mesh()
        net = self.net

        # the per-device parameter pack is the whole tree (params are
        # replicated), exchanged over the data axis, which is also the
        # only axis the flat zero1 / EF buffers vary over
        plan = self.exchange = ExchangePlan.from_config(
            self.config, exch_strategy
        ).bind(
            self.mesh.shape,
            n_elems=sum(
                math.prod(jnp.shape(l))
                for l in jax.tree.leaves(self.params)
            ),
            replica_axes=(DATA_AXIS,), flat_axes=(DATA_AXIS,),
            optimizer=self.optimizer,
        )
        opt_spec = self._opt_specs = (
            plan.opt_state_specs if plan.zero1 else P()
        )
        if plan.zero1 and self._restored.get("opt_state"):
            plan.check_restored_opt_state(self.opt_state, self._restored)
        elif plan.zero1:
            self.opt_state = plan.init_opt_state()
        if not plan.keeps_restored_ef(self.ef_state, self._restored):
            self.ef_state = plan.init_ef(self.mesh)
        ef_spec = plan.ef_specs

        def loss_fn(params, net_state, x, y, rng):
            out, new_state = net.apply(
                params, net_state, self.prep_input(x), train=True, rng=rng
            )
            with jax.named_scope("blk_head"):
                loss = self.compute_loss(out, y)
                err = 1.0 - accuracy(self.primary_logits(out), y)
            return loss, (new_state, err)

        def shard_train(params, net_state, opt_state, ef, x, y, lr, rng):
            rng = jax.random.fold_in(rng, lax.axis_index(DATA_AXIS))
            grad_fn = jax.value_and_grad(loss_fn, has_aux=True)
            (loss, (new_state, err)), grads = grad_fn(
                params, net_state, x, y, rng
            )
            # net_state (BN statistics) rides the same in-step reduce.
            # The reference kept per-GPU local stats with rare syncs to
            # save wire; here the stats are ~KBs vs the MB-scale grad
            # exchange XLA is already overlapping, so per-step sync is
            # free and keeps every replica's eval stats identical
            # (TM_DEBUG_SYNC relies on it).
            new_state = allreduce_mean(new_state, DATA_AXIS)
            loss = lax.pmean(loss, DATA_AXIS)
            err = lax.pmean(err, DATA_AXIS)
            params, opt_state, ef = plan.apply(
                params, grads, opt_state, ef, lr
            )
            return params, new_state, opt_state, ef, loss, err

        def shard_val(params, net_state, x, y):
            out, _ = net.apply(
                params, net_state, self.prep_input(x), train=False
            )
            logits = self.primary_logits(out)
            loss = lax.pmean(softmax_cross_entropy(logits, y), DATA_AXIS)
            err = lax.pmean(1.0 - accuracy(logits, y), DATA_AXIS)
            err5 = lax.pmean(1.0 - accuracy(logits, y, k=5), DATA_AXIS)
            return loss, err, err5

        rep = P()
        dp = P(DATA_AXIS)
        # TPU compiler knobs (utils/xla_options).  A bucketed exchange
        # adds the overlap preset (async collectives + latency-hiding
        # scheduler) — TPU meshes only (the CPU client rejects unknown
        # xla_tpu_* options), and only when the layout actually
        # bucketed: compiler_options otherwise stays None, or the jit
        # call churns the compile-cache key for nothing.
        is_tpu = self.mesh.devices.flat[0].platform == "tpu"
        self._compiler_options = xla_compiler_options(
            self.config, overlap=plan.bucketed and is_tpu
        )
        self._train_step = jax.jit(
            jax.shard_map(
                shard_train,
                mesh=self.mesh,
                in_specs=(rep, rep, opt_spec, ef_spec, dp, dp, rep, rep),
                out_specs=(rep, rep, opt_spec, ef_spec, rep, rep),
                check_vma=False,
            ),
            donate_argnums=(0, 1, 2, 3),
            compiler_options=self._compiler_options,
        )

        self._shard_train_body = shard_train
        self._device_cache = None
        self._train_step_cached = None
        self._train_scan = None
        self._scan_k = 0
        if self.config.get("device_data_cache"):
            self._init_device_cache()
        self._val_step = jax.jit(
            jax.shard_map(
                shard_val,
                mesh=self.mesh,
                in_specs=(rep, rep, dp, dp),
                out_specs=(rep, rep, rep),
                check_vma=False,
            )
        )

        # place params replicated on the mesh; opt state follows its
        # spec (data-sharded flat buffers under zero1, replicated else)
        rep_sharding = NamedSharding(self.mesh, P())
        self.params, self.net_state = jax.device_put(
            (self.params, self.net_state), rep_sharding
        )
        self.opt_state = jax.tree.map(
            lambda x, s: jax.device_put(x, NamedSharding(self.mesh, s)),
            self.opt_state,
            opt_spec if plan.zero1 else jax.tree.map(
                lambda _: P(), self.opt_state
            ),
        )
        self.ef_state = jax.tree.map(
            lambda x, s: jax.device_put(x, NamedSharding(self.mesh, s)),
            self.ef_state, ef_spec,
        )
        self._data_sharding = NamedSharding(self.mesh, P(DATA_AXIS))
        self._init_feed(self._data_sharding)

    # -- loss hooks (overridable; GoogLeNet adds aux-classifier terms) -----

    def prep_input(self, x):
        """Cast/transform the raw batch before the net sees it (default:
        cast to compute dtype; token-id models keep ints — see lstm.py).

        When the data object exposes ``device_mean`` (the u8 wire:
        batches arrive as uint8 crops), the mean-subtract runs HERE on
        device — it fuses into the first conv's input read, and the
        host + host->device link move 4x fewer bytes."""
        m = getattr(self.data, "device_mean", None)
        if m is not None:
            return x.astype(self.compute_dtype) - jnp.asarray(
                m, self.compute_dtype
            )
        return x.astype(self.compute_dtype)

    def primary_logits(self, out):
        """Extract the main logits from the net output (default: identity)."""
        return out

    def compute_loss(self, out, y):
        return softmax_cross_entropy(self.primary_logits(out), y)

    # -- iteration fns (reference: model.train_iter / val_iter) -----------

    def put_batch(self, batch):
        """Shard a host (x, y) batch onto the mesh's data axis — via
        the compile's :class:`~theanompi_tpu.data.HostStager`, the one
        copy of the transfer discipline (async puts, device ops
        labelled ``host_load``) shared by the train, val, and
        streaming-feed paths."""
        return self._stager.stage(batch)

    def _init_device_cache(self) -> None:
        """Stage the WHOLE train set into HBM once (``device_data_cache``
        config knob) when the data object supports it, and compile a
        fully device-resident step.

        TPU-native data residency: per-step host→device staging costs
        batch_bytes/step of host→device bandwidth; the dataset
        transfers once and each step gathers its batch on device.  The batch index comes from a
        DEVICE step counter + the staged epoch permutation, and the rng
        from ``fold_in(key0, step)`` — steady-state steps move ZERO
        bytes host→device.  The reference's analogue was RAM-cached
        pre-batched hickle files (SURVEY §2.1 ImageNet data row), one
        level down the memory hierarchy."""
        get = getattr(self.data, "dataset_arrays", None)
        with setup_phase("data"):   # a synthetic set is generated here
            arrays = get("train") if get is not None else None
        if arrays is None:
            import warnings

            warnings.warn(
                "device_data_cache requested but the data object does "
                "not expose dataset_arrays(); falling back to per-step "
                "staging",
                stacklevel=2,
            )
            return
        xs, ys = arrays
        rep = NamedSharding(self.mesh, P())
        with setup_phase("stage_data"):
            # floats ride in compute dtype (halves HBM); int inputs
            # (token ids) keep their dtype
            if np.issubdtype(np.asarray(xs).dtype, np.floating):
                xs = jnp.asarray(xs, self.compute_dtype)
            self._device_cache = (
                jax.device_put(jnp.asarray(xs), rep),
                jax.device_put(jnp.asarray(ys), rep),
            )

        gb = int(self.data.global_batch)
        n_shards = self.mesh.shape[DATA_AXIS]
        b_local = gb // n_shards
        body = self._shard_train_body

        def shard_cached(params, net_state, opt_state, ef, step,
                         xs, ys, perm, lr, key0):
            nb = perm.shape[0] // gb
            i = (step % nb).astype(jnp.int32)
            me = lax.axis_index(DATA_AXIS)
            start = i * gb + me * b_local
            idx = lax.dynamic_slice(perm, (start,), (b_local,))
            rng = jax.random.fold_in(key0, step)
            p, s, o, ef, loss, err = body(
                params, net_state, opt_state, ef, xs[idx], ys[idx],
                lr, rng
            )
            return p, s, o, ef, step + 1, loss, err

        rep_s, dp = P(), P(DATA_AXIS)
        osp = self._opt_specs  # zero1: data-sharded flat opt buffers
        efsp = self.exchange.ef_specs  # data-sharded EF residuals
        self._train_step_cached = jax.jit(
            jax.shard_map(
                shard_cached,
                mesh=self.mesh,
                in_specs=(rep_s, rep_s, osp, efsp, rep_s, rep_s,
                          rep_s, rep_s, rep_s, rep_s),
                out_specs=(rep_s, rep_s, osp, efsp, rep_s, rep_s,
                           rep_s),
                check_vma=False,
            ),
            donate_argnums=(0, 1, 2, 3, 4),
            compiler_options=self._compiler_options,
        )

        # multi-step scan: K steps per dispatch (``steps_per_call``
        # knob).  With the dataset device-resident the residual
        # per-step cost is HOST DISPATCH, so the worker hands the
        # device a K-step ``lax.scan`` and reads back K per-step
        # metrics lazily.  The math is the per-step body unchanged.
        self._scan_k = 0
        self._train_scan = None
        k = int(self.config.get("steps_per_call", 0) or 0)
        if k > 1:
            def shard_cached_scan(params, net_state, opt_state, ef,
                                  step, xs, ys, perm, lr, key0):
                def scan_body(carry, _):
                    p, s, o, e, st = carry
                    p, s, o, e, st, loss, err = shard_cached(
                        p, s, o, e, st, xs, ys, perm, lr, key0
                    )
                    return (p, s, o, e, st), (loss, err)

                (p, s, o, e, st), (losses, errs) = lax.scan(
                    scan_body,
                    (params, net_state, opt_state, ef, step),
                    None, length=k,
                )
                return p, s, o, e, st, losses, errs

            self._train_scan = jax.jit(
                jax.shard_map(
                    shard_cached_scan,
                    mesh=self.mesh,
                    in_specs=(rep_s, rep_s, osp, efsp) + (rep_s,) * 6,
                    out_specs=(rep_s, rep_s, osp, efsp) + (rep_s,) * 3,
                    check_vma=False,
                ),
                donate_argnums=(0, 1, 2, 3, 4),
                compiler_options=self._compiler_options,
            )
            self._scan_k = k
        self._step_dev = jax.device_put(jnp.zeros((), jnp.int32), rep)
        self._key0_dev = jax.device_put(
            jax.random.PRNGKey(self.seed + 7), rep
        )
        self._lr_dev = None
        self._lr_val = None
        self._perm_dev = None
        self._perm_src = None

    @property
    def train_step_fn(self):
        """The compiled SPMD train step:
        ``(params, net_state, opt_state, x, y, lr, rng) ->
        (params, net_state, opt_state, loss, err)``.
        Public so benchmarks/drivers can run unfenced step chains."""
        return self._train_step

    def train_step_cost_analysis(self):
        """XLA ``cost_analysis()`` of the ACTIVE train step — the
        cached-data variant when ``device_data_cache`` is live, else
        the staged-batch step, so FLOP counts describe the path
        ``train_iter`` actually runs.  Call after at least one
        ``train_iter`` (the cached path stages lr/permutation lazily);
        with a persistent compile cache the ``.compile()`` here
        deserializes the warmup step's executable instead of
        recompiling.  Always lowers the SINGLE-step variant: it is
        exact per step, whereas XLA's cost analysis counts a scanned
        loop body only once (measured: scan-of-K reports ~1x the body,
        not Kx), which would make the multi-step executable's number
        a misleading per-dispatch figure."""
        if self._train_step_cached is not None and self._perm_dev is not None:
            lowered = self._train_step_cached.lower(
                self.params, self.net_state, self.opt_state,
                self.ef_state, self._step_dev, self._device_cache[0],
                self._device_cache[1], self._perm_dev, self._lr_dev,
                self._key0_dev,
            )
        else:
            x, y = self.put_batch(self.data.train_batch(0))
            lowered = self._train_step.lower(
                self.params, self.net_state, self.opt_state,
                self.ef_state, x, y,
                jnp.float32(self.current_lr), self._rng,
            )
        return lowered.compile().cost_analysis()

    def train_step_hlo_text(self):
        """Optimized-HLO text of the ACTIVE training executable — the
        K-step scan when compiled (what ``train_chunk`` actually
        dispatches), else the cached/staged single step.  The
        step-phase profiler's scope-attribution source
        (``obs/profiler.py``): HLO instruction names are
        module-unique, so the text must come from the executable the
        profiled window runs.  Call after one warm ``train_chunk``."""
        from theanompi_tpu.utils.trace_comm import compiled_hlo_text

        if self._train_scan is not None and self._perm_dev is not None:
            lowered = self._train_scan.lower(
                self.params, self.net_state, self.opt_state,
                self.ef_state, self._step_dev, self._device_cache[0],
                self._device_cache[1], self._perm_dev, self._lr_dev,
                self._key0_dev,
            )
        elif (self._train_step_cached is not None
              and self._perm_dev is not None):
            lowered = self._train_step_cached.lower(
                self.params, self.net_state, self.opt_state,
                self.ef_state, self._step_dev, self._device_cache[0],
                self._device_cache[1], self._perm_dev, self._lr_dev,
                self._key0_dev,
            )
        else:
            x, y = self.put_batch(self.data.train_batch(0))
            lowered = self._train_step.lower(
                self.params, self.net_state, self.opt_state,
                self.ef_state, x, y,
                jnp.float32(self.current_lr), self._rng,
            )
        return compiled_hlo_text(lowered.compile())

    def train_chunk(self, count: int, k: int, recorder: Recorder) -> None:
        """Run steps ``count .. count+k-1``: ONE device dispatch when
        ``k`` matches the compiled scan length (amortizes host→device
        dispatch latency over k steps), else a per-step loop.  Records
        k per-step loss/err entries (lazy device scalars)."""
        if k != self._scan_k or self._train_scan is None:
            for j in range(k):
                self.train_iter(count + j, recorder)
            return
        with recorder.phase("load"):
            self._stage_cached_inputs()
        with recorder.phase("dispatch", first=count, k=k):
            (
                self.params,
                self.net_state,
                self.opt_state,
                self.ef_state,
                self._step_dev,
                losses,
                errs,
            ) = self._train_scan(
                self.params,
                self.net_state,
                self.opt_state,
                self.ef_state,
                self._step_dev,
                self._device_cache[0],
                self._device_cache[1],
                self._perm_dev,
                self._lr_dev,
                self._key0_dev,
            )
        # ONE vector record: k per-step metrics, one async D2H each
        recorder.train_error(count, losses, errs)

    def train_iter(self, count: int, recorder: Recorder) -> None:
        if self._train_step_cached is not None:
            # device-resident path: batches are ordered by the DEVICE
            # step counter (calls must be sequential, as the worker
            # loop's are); the only host work is restaging the epoch
            # permutation / lr when they change
            with recorder.phase("load"):
                self._stage_cached_inputs()
            with recorder.phase("dispatch", first=count, k=1):
                (
                    self.params,
                    self.net_state,
                    self.opt_state,
                    self.ef_state,
                    self._step_dev,
                    loss,
                    err,
                ) = self._train_step_cached(
                    self.params,
                    self.net_state,
                    self.opt_state,
                    self.ef_state,
                    self._step_dev,
                    self._device_cache[0],
                    self._device_cache[1],
                    self._perm_dev,
                    self._lr_dev,
                    self._key0_dev,
                )
            recorder.train_error(count, loss, err)
            return
        with recorder.phase("load"):
            if self._feed is not None:
                # pipelined feed: this batch was fetched + staged by
                # the producer thread UNDER the previous step's
                # compute — the wait segment is a ring pop
                x, y = self._feed.next(count)
            else:
                batch = self.data.train_batch(count)
                x, y = self.put_batch(batch)

        # NO per-step fence: the loss/err device scalars go to the
        # recorder unread and are materialized at the next print window
        # or epoch end (Recorder.fence).  Reading the value here would
        # serialize dispatch — the device idles while the host reads
        # back and stages the next batch — costing ~4% throughput on
        # the r1 flagship bench.  The recorder's flush reads the
        # values, and that read is the fence.
        with recorder.phase("dispatch", first=count, k=1):
            self._rng, step_key = jax.random.split(self._rng)
            (
                self.params,
                self.net_state,
                self.opt_state,
                self.ef_state,
                loss,
                err,
            ) = self._train_step(
                self.params,
                self.net_state,
                self.opt_state,
                self.ef_state,
                x,
                y,
                jnp.float32(self.current_lr),
                step_key,
            )
        recorder.train_error(count, loss, err)

    def val_iter(self, count: int, recorder: Recorder):
        batch = self.data.val_batch(count)
        x, y = self.put_batch(batch)
        loss, err, err5 = self._val_step(self.params, self.net_state, x, y)
        return float(loss), float(err), float(err5)

    # -- checkpoint / resume (reference: helper_funcs save/load) ----------

    def checkpoint_trees(self) -> dict[str, PyTree]:
        trees = {
            "params": self.params,
            "net_state": self.net_state,
            "opt_state": self.opt_state,
        }
        # the EF residual is worker state (compressed exchange): a
        # resume without it would re-inject nothing and diverge from
        # the uninterrupted run
        if getattr(self, "ef_state", None):
            trees["ef_state"] = self.ef_state
        return trees

    def _place_restored(self) -> None:
        if self.mesh is None:
            return
        rep = NamedSharding(self.mesh, P())
        self.params, self.net_state = jax.device_put(
            (self.params, self.net_state), rep
        )
        # opt state honors its compile-time layout (zero1: data-sharded
        # flat buffers; a blanket replicated put would silently undo
        # the sharded init the restore is supposed to preserve)
        osp = getattr(self, "_opt_specs", P())
        if isinstance(osp, P):
            osp = jax.tree.map(lambda _: osp, self.opt_state)
        self.opt_state = jax.tree.map(
            lambda x, s: jax.device_put(x, NamedSharding(self.mesh, s)),
            self.opt_state, osp,
        )
        if getattr(self, "ef_state", None):
            self.ef_state = jax.tree.map(
                lambda x, s: jax.device_put(
                    x, NamedSharding(self.mesh, s)
                ),
                self.ef_state, self.exchange.ef_specs,
            )
