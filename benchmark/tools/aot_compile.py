"""Rehearsal 3 of the ``on-chip-measurement`` guide: compile a cell's
programs at their REAL size for a described ``v5e:2x2``, without a
chip, and print ``memory_analysis`` — what fixes the batch sizes and
``n_blocks`` in the configuration files (their ``assumed`` quotes it).

    JAX_PLATFORMS=cpu python3 -m benchmark.tools.aot_compile <cell> [key=value ...]

``key=value`` overrides a key of the configuration's ``program`` group
(``batch_size=4``) or, with a ``decoder.`` prefix, of
``serving.decoder`` (``decoder.n_blocks=12000``) for this compile only.

The program builds its mesh from real devices and places its own
parameters, which a described device cannot hold.  So this script —
and nothing in the program — stands in for the placement while the
model is built: ``jax.device_put`` and a jitted call with
``out_shardings`` return shapes with their shardings instead of
arrays, and ``ops.attention._on_tpu`` says yes.  The step is then
lowered from those shapes and compiled by the TPU compiler that ships
with jaxlib.  Nothing runs, so nothing here is a time or a result.
"""

from __future__ import annotations

import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax                                  # noqa: E402
import jax.numpy as jnp                     # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402


def _sds(x, sharding):
    return jax.ShapeDtypeStruct(jnp.shape(x), jnp.result_type(x),
                                sharding=sharding)


def _stand_in_for_placement():
    """See the module docstring.  Returns nothing; patches ``jax``."""
    import theanompi_tpu.models.base      # noqa: F401  real decorators first
    import theanompi_tpu.models.llama     # noqa: F401
    import theanompi_tpu.serving          # noqa: F401
    from theanompi_tpu.ops import attention

    attention._on_tpu = lambda: True
    real_jit = jax.jit

    def device_put(x, device=None, **_):
        if isinstance(device, jax.sharding.Sharding):
            return jax.tree.map(lambda leaf: _sds(leaf, device), x)
        return jax.tree.map(
            lambda leaf, s: _sds(leaf, s), x, device
        )

    class ShapeJit:
        def __init__(self, fn, **kw):
            self._fn, self._kw = fn, kw
            self._jit = real_jit(fn, **kw)

        def lower(self, *a, **k):
            return self._jit.lower(*a, **k)

        def __call__(self, *a, **k):
            out_sh = self._kw.get("out_shardings")
            if out_sh is None:
                return self._jit(*a, **k)
            shapes = jax.eval_shape(self._fn, *a, **k)
            return jax.tree.map(
                lambda s, sh: _sds(s, sh), shapes, out_sh,
            )

    jax.device_put = device_put
    jax.jit = lambda fn=None, **kw: (
        ShapeJit(fn, **kw) if fn is not None
        else (lambda f: ShapeJit(f, **kw))
    )


def _report(name: str, compiled) -> dict:
    m = compiled.memory_analysis()
    text = compiled.as_text()
    out = {
        "program": name,
        "argument_gb": m.argument_size_in_bytes / 1e9,
        "output_gb": m.output_size_in_bytes / 1e9,
        "alias_gb": m.alias_size_in_bytes / 1e9,
        "temp_gb": m.temp_size_in_bytes / 1e9,
        "code_gb": m.generated_code_size_in_bytes / 1e9,
        "tpu_custom_calls": text.count("tpu_custom_call"),
        "all_reduces": text.count(" all-reduce("),
    }
    out["live_gb"] = (out["argument_gb"] + out["output_gb"]
                      - out["alias_gb"] + out["temp_gb"] + out["code_gb"])
    print(json.dumps(out), flush=True)
    return out


def compile_cell(cell_name: str, overrides: dict) -> list[dict]:
    from jax.experimental import topologies

    from ..run import load_cell

    cell = load_cell(cell_name)
    config = cell["config"]
    for key, value in overrides.items():
        group = config["program"]
        if key.startswith("decoder."):
            group, key = config["serving"]["decoder"], key[len("decoder."):]
        group[key] = value
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    devices = list(topo.devices)[:cell["chips"]]
    _stand_in_for_placement()

    from theanompi_tpu.parallel import dp_replicas, make_mesh

    if cell["traffic"]["kind"] == "train":
        import importlib

        from ..drivers.train import program_config

        cfg = program_config(config, seed=0, n_replicas=len(devices))
        mesh = make_mesh(data=len(devices), devices=devices)
        model = getattr(
            importlib.import_module(config["model"]["modelfile"]),
            config["model"]["modelclass"],
        )(cfg)
        model.build_model(n_replicas=dp_replicas(mesh))
        model.compile_iter_fns(mesh=mesh, exch_strategy=cfg["exch_strategy"])
        model._stage_cached_inputs()
        if hasattr(model, "_seqs_dev"):     # Llama's scan
            args = (model.params, model.opt_state, model.ef_state,
                    model._step_dev, model._seqs_dev, model._perm_dev,
                    model._lr_dev)
        else:                               # ClassifierModel's scan
            args = (model.params, model.net_state, model.opt_state,
                    model.ef_state, model._step_dev, *model._device_cache,
                    model._perm_dev, model._lr_dev, model._key0_dev)
        staged = sum(
            a.size * a.dtype.itemsize
            for a in jax.tree.leaves(args[4 if hasattr(model, "_seqs_dev")
                                          else 5:][:2])
        )
        print(json.dumps({"batch_size": cfg["batch_size"],
                          "steps_per_call": cfg["steps_per_call"],
                          "n_train": cfg["n_train"],
                          "staged_gb_per_chip": staged / 1e9}), flush=True)
        return [_report("train_scan", model._train_scan.lower(*args).compile())]

    from theanompi_tpu.models.llama import Llama

    from ..drivers.open_loop import program_config

    mesh = make_mesh(data=1, model=1, devices=devices[:1])
    model = Llama(program_config(config, seed=0))
    model.build_model(n_replicas=1)
    model.compile_iter_fns(mesh=mesh)
    decoder = model.make_decoder(paged=True, **config["serving"]["decoder"])
    rep = NamedSharding(mesh, P())

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=rep)

    s, mb = decoder.max_slots, decoder.max_blocks
    decode = decoder._decode_jit(True).lower(
        model.params, decoder.pools, arg((s, mb), jnp.int32),
        arg((s,), jnp.int32), arg((s,), jnp.int32), arg((s, 2), jnp.uint32),
        arg((s,), jnp.float32), arg((s,), jnp.bool_),
    ).compile()
    prefill = decoder._prefill_jit(True).lower(
        model.params, decoder.pools, arg((mb,), jnp.int32),
        arg((decoder.prefill_chunk,), jnp.int32), arg((), jnp.int32),
        arg((), jnp.int32), arg((2,), jnp.uint32), arg((), jnp.float32),
    ).compile()
    return [_report("decode", decode), _report("prefill_chunk", prefill)]


def main(argv: list[str]) -> int:
    overrides = {}
    for kv in argv[1:]:
        key, value = kv.split("=", 1)
        overrides[key] = json.loads(value)
    compile_cell(argv[0], overrides)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
