"""``tools/aot_compile.py`` for a training cell whose step takes state
beside parameters and optimizer state (``Llama.net_state``: a sigmoid
router's selection bias — the ``glm47flash`` and ``nemotron3`` cells),
which that tool's argument list leaves out:

    JAX_PLATFORMS=cpu python3 -m benchmark.tools.aot_compile_stateful \
        <cell> [key=value ...]

The same stand-in for the placement, the same report line; the scan is
lowered with ``model._state_args()`` after the rate.  Nothing runs, so
nothing here is a time or a result.
"""

from __future__ import annotations

import importlib
import json
import sys

from . import aot_compile as aot


def compile_cell(cell_name: str, overrides: dict) -> dict:
    from jax.experimental import topologies

    from ..drivers.train import program_config
    from ..run import load_cell

    cell = load_cell(cell_name)
    config = cell["config"]
    config["program"].update(overrides)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    devices = list(topo.devices)[:cell["chips"]]
    aot._stand_in_for_placement()

    from theanompi_tpu.parallel import dp_replicas, make_mesh

    cfg = program_config(config, seed=0, n_replicas=len(devices))
    mesh = make_mesh(data=len(devices), devices=devices)
    model = getattr(importlib.import_module(config["model"]["modelfile"]),
                    config["model"]["modelclass"])(cfg)
    model.build_model(n_replicas=dp_replicas(mesh))
    model.compile_iter_fns(mesh=mesh, exch_strategy=cfg["exch_strategy"])
    model._stage_cached_inputs()
    args = (model.params, model.opt_state, model.ef_state, model._step_dev,
            model._seqs_dev, model._perm_dev, model._lr_dev,
            *model._state_args())
    print(json.dumps({"batch_size": cfg["batch_size"],
                      "steps_per_call": cfg["steps_per_call"],
                      "remat_kept": [model.remat_kept_calls,
                                     model.remat_kept_attn_calls,
                                     model.remat_kept_moe_calls],
                      "step_peak_estimate_gb":
                          model.step_peak_estimate() / 1e9,
                      "local_params": model._local_params(mesh.shape)[0]}),
          flush=True)
    return aot._report("train_scan", model._train_scan.lower(*args).compile())


def main(argv: list[str]) -> int:
    overrides = {}
    for kv in argv[1:]:
        key, value = kv.split("=", 1)
        overrides[key] = json.loads(value)
    compile_cell(argv[0], overrides)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
