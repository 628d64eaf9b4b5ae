"""Observability subsystem: distributed span tracing (``tracer.py``:
bounded flight-recorder; ``export.py``: Perfetto export with counter
tracks, critical-path attribution), the step-phase profiler
(``profiler.py``), Prometheus-style metrics text (``metrics.py``),
the process's compile counter (``compile_meter.py``), a training
run's set-up phases (``setup.py``) and its memory account
(``memory.py``), a MoE step's routing counters
(``routing.py``), a looped decoder's exit counters (``exits.py``), a
mamba stack's scan counters (``ssm.py``) and an attention gate's
counters (``gate.py``).
See docs/OBSERVABILITY.md; what reads these on the chip is under
``benchmark/`` (PERF.md section 3)."""

from theanompi_tpu.obs.tracer import (  # noqa: F401
    DEFAULT_TRACE_SAMPLE,
    Tracer,
    child_context,
    force_sample,
    make_context,
)
from theanompi_tpu.obs.compile_meter import (  # noqa: F401
    CompileMeter,
    process_meter,
)
from theanompi_tpu.obs.setup import (  # noqa: F401
    SetupRecord,
    begin_setup,
    last_process_phases,
    last_setup_phases,
    setup_phase,
)
from theanompi_tpu.obs.memory import (  # noqa: F401
    MemoryAccount,
    begin_memory_account,
    last_memory_account,
)
from theanompi_tpu.obs.routing import last_moe_counters  # noqa: F401
from theanompi_tpu.obs.exits import last_ut_counters  # noqa: F401
from theanompi_tpu.obs.ssm import last_ssm_counters  # noqa: F401
from theanompi_tpu.obs.gate import last_gate_counters  # noqa: F401
from theanompi_tpu.obs.export import (  # noqa: F401
    chrome_trace,
    critical_path,
    format_critical_path,
    span_tree,
    write_chrome_trace,
)
from theanompi_tpu.obs.metrics import (  # noqa: F401
    quantile_samples,
    render_metrics,
)
from theanompi_tpu.obs.profiler import (  # noqa: F401
    StepProfile,
    format_profile,
    gap_attribution,
    profile_scope_sets,
    step_profile,
)

__all__ = [
    "CompileMeter",
    "DEFAULT_TRACE_SAMPLE",
    "MemoryAccount",
    "SetupRecord",
    "StepProfile",
    "Tracer",
    "begin_memory_account",
    "begin_setup",
    "child_context",
    "chrome_trace",
    "critical_path",
    "force_sample",
    "format_critical_path",
    "format_profile",
    "gap_attribution",
    "last_gate_counters",
    "last_memory_account",
    "last_moe_counters",
    "last_process_phases",
    "last_setup_phases",
    "last_ssm_counters",
    "last_ut_counters",
    "make_context",
    "process_meter",
    "profile_scope_sets",
    "quantile_samples",
    "render_metrics",
    "setup_phase",
    "span_tree",
    "step_profile",
    "write_chrome_trace",
]
