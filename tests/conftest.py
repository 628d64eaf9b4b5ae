"""Test config: the whole suite runs on a virtual 16-device CPU mesh.

The reference tested on real multi-GPU clusters with no fakes (SURVEY
§4); the rebuild tests every collective on virtual CPU devices so the
suite runs anywhere.  Both settings below must exist BEFORE ``import
jax`` creates a backend:

- ``JAX_PLATFORMS=cpu`` (setdefault): a bare ``pytest`` on the chip
  machine never takes the chip — a chip belongs to one process, and
  it is ``chip_smoke.py``'s.  Kernels are tested in the Pallas
  interpreter, and compiled for a described chip in
  ``test_chip_compile.py``.
- ``--xla_force_host_platform_device_count=16``: most tests use the
  first 8 devices; the true-4-D llama layout (dp=2 x tp=2 x sp=2 x
  pp=2) needs 16.
"""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=16"
    ).strip()

import jax  # noqa: E402

# persistent compile cache: repeat suite runs skip most XLA compiles;
# shared location with the other entry points so all warm each other
from theanompi_tpu.utils import enable_compile_cache  # noqa: E402

enable_compile_cache()

import pytest  # noqa: E402


def pytest_addoption(parser):
    parser.addoption(
        "--runslow", action="store_true", default=False,
        help="run the slow tier (multi-process runs, convergence "
        "training, heavy multi-layout compiles)",
    )


def pytest_collection_modifyitems(config, items):
    """Two test tiers (VERDICT r3 #8): the DEFAULT invocation
    (``pytest -q tests/``) must finish in minutes on a 1-core host —
    every compile in it is one the persistent cache amortizes.  The
    slow tier (``--runslow`` or ``TM_SLOW_TESTS=1``) adds the
    multi-process drills and convergence runs; docs/PODS.md documents
    both wall times."""
    if config.getoption("--runslow") or os.environ.get(
        "TM_SLOW_TESTS"
    ) == "1":
        return
    skip = pytest.mark.skip(
        reason="slow tier: pass --runslow (or TM_SLOW_TESTS=1)"
    )
    for item in items:
        if "slow" in item.keywords:
            item.add_marker(skip)


@pytest.fixture(scope="session")
def devices8():
    devs = jax.devices("cpu")
    assert len(devs) >= 8, f"expected 8 fake devices, got {len(devs)}"
    return devs[:8]


@pytest.fixture(scope="session")
def devices16():
    devs = jax.devices("cpu")
    if len(devs) < 16:
        pytest.skip(f"needs 16 fake devices, have {len(devs)}")
    return devs[:16]


@pytest.fixture()
def mesh8(devices8):
    from theanompi_tpu.parallel import make_mesh

    return make_mesh(data=8, devices=devices8)


@pytest.fixture()
def rng():
    import numpy as np

    return np.random.default_rng(0)
