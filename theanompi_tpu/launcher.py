"""Process launcher + ``tmlauncher`` CLI.

TPU-native replacement of the reference's launcher (reference:
``theanompi/launcher.py`` + ``tmlauncher`` console entry): where the
reference assembled ``mpirun -np N ... python -m theanompi.bsp_worker
<device> <modelfile> <modelclass>``, this launcher either

- runs the worker **in-process** (single-controller SPMD — one Python
  process drives every local chip; no mpirun needed at all on a single
  host), or
- spawns ONE detached controller subprocess (so ``rule.init()`` returns
  immediately and ``rule.wait()`` joins, matching reference behavior), or
- for multi-host pods: ``tmlauncher --coordinator host:port
  --num-hosts H --host-id I ...`` runs on every host and calls
  ``jax.distributed.initialize`` — the mpirun/NCCL-clique replacement;
  XLA then treats the whole pod as one mesh.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import subprocess
import sys
from dataclasses import dataclass
from typing import Any, Optional, Sequence


@dataclass
class LaunchHandle:
    mode: str
    proc: Optional[subprocess.Popen] = None
    result: Any = None
    supervisor: Any = None  # utils.supervisor.Supervisor (supervised)

    def wait(self) -> Any:
        if self.mode == "supervised" and self.supervisor is not None:
            # blocks through failures: relaunches with resume=True
            # until clean completion or the restart budget is spent
            # (then utils.supervisor.SupervisorGaveUp propagates);
            # returns the supervision report (restart causes, MTTR)
            self.result = self.supervisor.run()
            return self.result
        if self.mode == "subprocess" and self.proc is not None:
            rc = self.proc.wait()
            if rc != 0:
                raise RuntimeError(f"worker process exited with code {rc}")
            return rc
        return self.result

    def poll(self) -> Optional[int]:
        if self.mode == "supervised" and self.supervisor is not None:
            p = self.supervisor.proc
            return p.poll() if p is not None else None
        if self.proc is not None:
            return self.proc.poll()
        return 0


def _run_worker_inprocess(
    worker_module: str,
    devices: Sequence[Any] | None,
    modelfile: str,
    modelclass: str,
    rule_kwargs: dict,
) -> Any:
    mod = importlib.import_module(worker_module)
    return mod.run(
        devices=devices,
        modelfile=modelfile,
        modelclass=modelclass,
        **rule_kwargs,
    )


def launch(
    worker_module: str,
    devices: Sequence[Any] | None,
    modelfile: str,
    modelclass: str,
    mode: str = "subprocess",
    rule_kwargs: dict | None = None,
    supervise: dict | None = None,
    elastic: dict | bool | None = None,
) -> LaunchHandle:
    """``mode="supervised"`` (or any ``supervise={...}`` kwargs) wraps
    the worker subprocess in ``utils.supervisor.Supervisor``: worker
    exits are classified (clean / preemption-like 137 / crash), hangs
    are detected by heartbeat stall and killed, and every failure
    relaunches with ``resume=True`` into the same ``checkpoint_dir``
    under exponential backoff — no operator in the loop.  ``wait()``
    then returns the supervision report; the restart budget spending
    out raises ``SupervisorGaveUp`` (loud, never a silent loop).
    ``supervise`` keys = ``Supervisor`` kwargs (``max_restarts``,
    ``stall_timeout_s``, ``backoff_base_s``, ``crash_loop_budget``,
    ...).

    ``elastic`` (implies supervised) makes the run survive PERMANENT
    capacity loss by resizing the world instead of waiting: each
    relaunch probes the available device count and runs at that
    width, the worker reshards its checkpoint onto the new layout
    (``config["elastic"]`` is set for it), and the report carries the
    per-launch ``world_size_history``.  Pass ``True`` or a dict:
    ``{"min_dp": 2}`` bounds how far the world may shrink
    (``tmlauncher --elastic-min-dp``); see docs/RESILIENCE.md."""
    rule_kwargs = dict(rule_kwargs or {})
    if supervise is None:
        # rule.init(..., launch="supervised", supervise={...}) arrives
        # through rule_kwargs — pull it out before it reaches run()
        supervise = rule_kwargs.pop("supervise", None)
    if elastic is None:
        elastic = rule_kwargs.pop("elastic", None)
    if elastic:
        el = dict(elastic) if isinstance(elastic, dict) else {}
        n_dev = (
            len(devices) if devices is not None else el.get("n_devices")
        )
        if not n_dev:
            raise ValueError(
                "elastic launch needs an explicit baseline world: "
                "pass devices=[...] or elastic={'n_devices': N}"
            )
        supervise = dict(supervise or {})
        supervise.setdefault("elastic", True)
        supervise.setdefault("elastic_min_dp", int(el.get("min_dp", 1)))
        supervise.setdefault("n_devices", int(n_dev))
        # the worker side of elasticity: reshard on load + batch/LR
        # policy (workers/bsp_worker._apply_elastic_policy)
        cfg = dict(rule_kwargs.get("config") or {})
        cfg.setdefault("elastic", True)
        rule_kwargs["config"] = cfg
        mode = "supervised"
    if mode == "supervised" or supervise is not None:
        from theanompi_tpu.utils.supervisor import (
            Supervisor,
            make_worker_cmd_factory,
        )

        checkpoint_dir = rule_kwargs.get("checkpoint_dir")
        if not checkpoint_dir:
            raise ValueError(
                "supervised launch needs rule_kwargs['checkpoint_dir'] "
                "— relaunch-with-resume is the whole recovery story"
            )
        sup = Supervisor(
            cmd_for=make_worker_cmd_factory(
                worker_module, devices, modelfile, modelclass,
                rule_kwargs,
            ),
            checkpoint_dir=checkpoint_dir,
            initial_resume=bool(rule_kwargs.get("resume", False)),
            **(supervise or {}),
        )
        return LaunchHandle(mode="supervised", supervisor=sup)
    if mode == "inprocess":
        result = _run_worker_inprocess(
            worker_module, devices, modelfile, modelclass, rule_kwargs
        )
        return LaunchHandle(mode=mode, result=result)
    if mode == "subprocess":
        spec = {
            "devices": list(devices) if devices is not None else None,
            "modelfile": modelfile,
            "modelclass": modelclass,
            "kwargs": rule_kwargs,
        }
        cmd = [
            sys.executable,
            "-m",
            worker_module,
            "--spec-json",
            json.dumps(spec),
        ]
        proc = subprocess.Popen(cmd, env=os.environ.copy())
        return LaunchHandle(mode=mode, proc=proc)
    raise ValueError(f"unknown launch mode {mode!r}")


def worker_main(run_fn) -> Any:
    """Entry for ``python -m theanompi_tpu.workers.X --spec-json ...``."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--spec-json", required=True)
    ns = ap.parse_args()
    spec = json.loads(ns.spec_json)
    from theanompi_tpu.utils import enable_compile_cache

    enable_compile_cache()
    return run_fn(
        devices=spec.get("devices"),
        modelfile=spec["modelfile"],
        modelclass=spec["modelclass"],
        **spec.get("kwargs", {}),
    )


# ---------------------------------------------------------------------------
# tmlauncher CLI (reference: `tmlauncher` console script)
# ---------------------------------------------------------------------------

def init_distributed(
    coordinator: Optional[str],
    num_hosts: Optional[int],
    host_id: Optional[int],
) -> None:
    """Join a multi-host pod run. Replaces the reference's mpirun +
    NCCL-clique bootstrap with ``jax.distributed.initialize`` over DCN."""
    if coordinator is None:
        return
    import jax

    jax.distributed.initialize(
        coordinator_address=coordinator,
        num_processes=num_hosts,
        process_id=host_id,
    )


def finish_distributed(ok: bool = True) -> None:
    """Exit a multi-process worker WITHOUT the coordination-service
    shutdown barrier.

    ``jax.distributed.shutdown`` runs a barrier over every task; when
    a peer died mid-run (preemption, ``TM_FAULT_AT`` drills), that
    barrier can never succeed — the error poller then HARD-ABORTS the
    surviving processes (observed: ``client.h:80 Terminating process
    ... another task died``) *after* they finished training and wrote
    checkpoints, turning a completed run into exit code 1.  The async
    rules are peer-death-tolerant BY DESIGN (the TCP center/gossip
    planes shrug off a dead worker); teardown must be too.

    Call at the very end of a distributed worker ``__main__``: flushes
    stdio AND a terminal heartbeat, then ``os._exit``s, skipping the
    barrier.  The heartbeat stamp is what lets a supervisor
    distinguish "clean exit" from "died during shutdown" on this
    no-barrier path — without it an ``os._exit`` and a SIGKILL during
    teardown look identical.  Restart tooling judges the run by its
    checkpoint + exit code + final heartbeat, which this makes
    truthful.  No-op under a single process (normal interpreter exit
    is fine there)."""
    import jax

    if jax.process_count() <= 1:
        return
    from theanompi_tpu.utils import supervisor as _sup

    _sup.flush_final_heartbeat(ok=ok)
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(0 if ok else 1)


def main(argv: Sequence[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        prog="tmlauncher",
        description="Launch theanompi_tpu training (mpirun replacement).",
    )
    ap.add_argument("rule", choices=["BSP", "EASGD", "GOSGD"])
    ap.add_argument("modelfile", help="e.g. theanompi_tpu.models.wresnet")
    ap.add_argument("modelclass", help="e.g. WResNet")
    ap.add_argument("--devices", type=int, default=None,
                    help="number of local chips to use (default: all)")
    ap.add_argument("--coordinator", default=None,
                    help="host:port of process 0 for multi-host runs")
    ap.add_argument("--num-hosts", type=int, default=None)
    ap.add_argument("--host-id", type=int, default=None)
    ap.add_argument("--kwargs", default="{}",
                    help="JSON dict of extra rule/worker kwargs")
    ap.add_argument("--supervise", action="store_true",
                    help="self-healing mode: run the worker under the "
                    "supervisor (auto-relaunch with resume on "
                    "crash/preemption, hang watchdog); needs "
                    "checkpoint_dir in --kwargs")
    ap.add_argument("--max-restarts", type=int, default=5,
                    help="supervisor restart budget (with --supervise)")
    ap.add_argument("--stall-timeout-s", type=float, default=120.0,
                    help="supervisor hang watchdog: kill + relaunch "
                    "after this many seconds without a heartbeat "
                    "(with --supervise)")
    ap.add_argument("--elastic-min-dp", type=int, default=None,
                    help="elastic self-healing (implies --supervise): "
                    "relaunch at the surviving device count after a "
                    "permanent capacity loss, resharding the "
                    "checkpoint onto the new layout, down to this "
                    "minimum dp; needs --devices (the baseline world) "
                    "and checkpoint_dir in --kwargs")
    ns = ap.parse_args(argv)

    if ns.elastic_min_dp is not None:
        if ns.devices is None:
            ap.error(
                "--elastic-min-dp needs --devices N (the baseline "
                "world size the run starts at)"
            )
        ns.supervise = True

    if ns.supervise and ns.coordinator is not None:
        # the supervised child is spawned WITHOUT the coordinator
        # bootstrap, so each host would silently train an independent
        # single-host replica into the shared checkpoint_dir —
        # refuse instead of degrading.  Multi-host self-healing =
        # per-host supervisors under the pod orchestrator's job-level
        # restart (docs/RESILIENCE.md).
        ap.error(
            "--supervise does not compose with --coordinator yet: "
            "run one supervised tmlauncher per host WITHOUT "
            "--coordinator, or let the pod orchestrator restart the "
            "whole job"
        )

    from theanompi_tpu.utils import enable_compile_cache

    enable_compile_cache()
    init_distributed(ns.coordinator, ns.num_hosts, ns.host_id)

    import theanompi_tpu as tm

    rule = getattr(tm, ns.rule)()
    devices = list(range(ns.devices)) if ns.devices is not None else None
    extra: dict = {}
    if ns.supervise:
        extra["supervise"] = {
            "max_restarts": ns.max_restarts,
            "stall_timeout_s": ns.stall_timeout_s,
        }
    if ns.elastic_min_dp is not None:
        extra["elastic"] = {"min_dp": ns.elastic_min_dp}
    rule.init(
        devices=devices,
        modelfile=ns.modelfile,
        modelclass=ns.modelclass,
        launch="supervised" if ns.supervise else "inprocess",
        **extra,
        **json.loads(ns.kwargs),
    )
    rule.wait()
    if ns.coordinator is not None:
        # never let the shutdown barrier undo a completed run (a dead
        # peer makes it unpassable; skipping it is safe for live ones)
        finish_distributed(ok=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
