"""The contract between the program and ``benchmark/``: what the
benchmark's readers join on has to be what the program emits, and
what ``BENCHMARK.json`` names has to be in the tree.

``benchmark/tests`` holds the readers to recorded chip traces and is
outside tier-1 (it takes minutes); nothing there runs when a PR
renames a ``jax.named_scope`` in ``theanompi_tpu/``.  A renamed scope
nulls a per-layer metric on the chip, and the driver then refuses
every later ``benchmark`` PR that leaves it so.  Here each reader runs
end to end on the REAL compiled text of its cell's training step (the
configuration's own rehearsal sizes, on the CPU) under a made-up
trace in which every instruction of that text ran for a microsecond:
no scope name is typed in this file, so the readers and the program
can only agree or fail.  Counts and names only: no time here means
anything.

Left to the chip and to ``tests/test_chip_compile.py``: the readers of
Mosaic kernels (``*_roofline``), whose ``tpu_custom_call``s the CPU's
text does not hold; left to ``tests/test_training_spans.py``: the
``tm:`` spans and ``setup.*`` phases.
"""

import functools
import importlib
import inspect
import json
import re
import sys
from pathlib import Path

import pytest

from benchmark import hlo_read
from benchmark.drivers.train import program_config

ROOT = Path(__file__).resolve().parents[1]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CONFIGS = {c["name"]: c for c in BENCH["configs"]}
CELLS = {w["name"]: w for w in BENCH["workloads"]}
#: the helpers that join a trace on the program's ``named_scope``s,
#: and what of theirs gives a time
SCOPE_HELPERS = ("_blocks", "_scopes", "_ut", "_moe")
SCOPE_TIMES = ("block_ms", "phase_ms", "opt_ms", "named_share",
               "scope_ms", "scope_seconds")


def reader_of(metric: str):
    return importlib.import_module(f"benchmark.layer_metrics.{metric}")


def joins_on_scopes(metric: str) -> bool:
    return any(
        inspect.isfunction(v) and v.__name__ in SCOPE_TIMES
        and v.__module__.rsplit(".", 1)[-1] in SCOPE_HELPERS
        for v in vars(reader_of(metric)).values()
    )


def scope_cases() -> list[tuple[str, str]]:
    """``(configuration, metric)`` for every scope-joined per-layer
    metric and every configuration one of its cells runs."""
    return sorted({
        (CELLS[cell]["config"], m["name"])
        for m in BENCH["per_layer"] if joins_on_scopes(m["name"])
        for cell in m["workloads"]
    })


def load_config(name: str, rehearsal: bool = False) -> dict:
    config = json.loads((ROOT / CONFIGS[name]["file"]).read_text())
    return dict(config, **config["rehearsal"]) if rehearsal else config


def model_class(config: dict):
    from theanompi_tpu.workers.bsp_worker import _resolve_model

    return _resolve_model(config["model"]["modelfile"],
                          config["model"]["modelclass"])


@functools.lru_cache(maxsize=None)
def step_facts(config_name: str) -> dict:
    """The facts a traced run of the configuration would hand its
    readers, at the rehearsal sizes: the step's compiled text (one
    compile a configuration, by the method the driver calls) and a
    trace of one run of it, a microsecond an instruction."""
    import jax

    from theanompi_tpu.parallel import make_mesh

    config = load_config(config_name, rehearsal=True)
    model = model_class(config)(
        program_config(config, seed=3, n_replicas=1))
    model.build_model(n_replicas=1)
    model.compile_iter_fns(
        mesh=make_mesh(devices=jax.devices()[:1], data=1))
    text = model.train_step_hlo_text()
    names = [m.group(1) for m in map(hlo_read._INSTR.match,
                                     text.splitlines()) if m]
    us = 10 ** 6        # picoseconds
    ops = [[name, (i + 1) * us, (i + 2) * us]
           for i, name in enumerate(names)]
    return {
        "hlo_text": text, "scan_k": 1,
        "cell": {"name": config_name, "config": config},
        # a roofline over a scope (``ssd_scan_roofline``) reads them
        "peaks": json.loads(
            (ROOT / "benchmark" / "peaks.json").read_text())["TPU v5 lite"],
        "trace": {"devices": {"/device:TPU:0": {
            "ops": ops,
            "modules": [["jit_step(1)", 0, (len(ops) + 2) * us]],
        }}, "host": [], "text": {}},
    }


@pytest.mark.parametrize("config_name, metric", scope_cases())
def test_the_scope_a_reader_joins_on_is_in_the_programs_step(
        config_name, metric):
    """A reader of a cell finds instructions of that cell's step: the
    scope it looks for is one the program still opens, around work
    that still reaches the compiled text under that name."""
    got = reader_of(metric).read(step_facts(config_name))
    assert got is not None and got > 0, (
        f"{metric} reads nothing from the step of {config_name}: the "
        f"scope it joins on (benchmark/layer_metrics/{metric}.py) is "
        "not in the program's compiled text any more")


def test_there_are_at_least_twenty_such_pairs_over_six_configurations():
    cases = scope_cases()
    assert len(cases) >= 20
    assert {c for c, _ in cases} == set(CONFIGS)


# -- BENCHMARK.json agrees with the tree -------------------------------------


def _sources_that_read_the_config(cls) -> str:
    """The text of the modules that hold the class and its bases, and
    of the exchange plan those build from the same dict
    (``ExchangePlan.from_config``)."""
    from theanompi_tpu.parallel import plan

    modules = {sys.modules[k.__module__] for k in cls.__mro__
               if k.__module__.startswith("theanompi_tpu")}
    return "\n".join(inspect.getsource(m) for m in modules | {plan})


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_a_cell_names_files_a_class_and_keys_that_exist(cell):
    spec = CELLS[cell]
    assert spec["chips"] in (1, 4)
    config = load_config(spec["config"])
    traffic = json.loads(
        (ROOT / "benchmark" / "traffic" / f"{spec['traffic']}.json")
        .read_text())
    assert importlib.import_module(
        f"benchmark.drivers.{traffic['kind']}").run
    cls = model_class(config)
    assert inspect.isclass(cls)
    # a key nobody reads is a typo that trains another model at full
    # speed: a textual check is enough for that
    source = _sources_that_read_the_config(cls)
    knobs = program_config(config, seed=1, n_replicas=spec["chips"])
    unread = [k for k in knobs
              if not re.search(rf"""["']{re.escape(k)}["']""", source)]
    assert not unread, f"{cell}: no model module reads {unread}"
    # the rehearsal sizes are the same program under other numbers
    small = program_config(load_config(spec["config"], rehearsal=True),
                           seed=1, n_replicas=1)
    assert set(small) == set(knobs)
