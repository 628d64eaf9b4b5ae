"""theanompi_tpu — a TPU-native distributed training framework.

A from-scratch rebuild of the capabilities of ``printedheart/Theano-MPI``
(a fork of ``uoguelph-mlrg/Theano-MPI``, arXiv:1605.08325) designed for
TPU hardware: JAX/XLA for single-device compute, ``jax.sharding`` +
``shard_map`` collectives over ICI for parameter exchange, and
``jax.distributed`` for multi-host orchestration.

User-facing API mirrors the reference's rule classes
(reference: ``theanompi/__init__.py`` exports ``BSP``, ``EASGD``, ``GOSGD``):

    from theanompi_tpu import BSP
    rule = BSP()
    rule.init(devices=['tpu0', 'tpu1'],
              modelfile='theanompi_tpu.models.wresnet',
              modelclass='WResNet')
    rule.wait()

Unlike the reference (one OS process per GPU driven by mpirun), the
TPU-native design is single-controller SPMD: one Python process per host
drives all local chips through a `jax.sharding.Mesh`; the BSP "exchanger"
is a `lax.pmean` inside the jitted train step, which XLA overlaps with
backprop automatically.
"""

import time as _time

_t_import = _time.monotonic()

from theanompi_tpu.version import __version__  # noqa: E402
from theanompi_tpu.rules import BSP, EASGD, GOSGD  # noqa: E402

#: start and end of this import on ``time.monotonic``: two of the
#: process stamps ``obs/setup.py`` reports (``process_phases``)
_IMPORT_SPAN = (_t_import, _time.monotonic())

__all__ = ["BSP", "EASGD", "GOSGD", "__version__"]
