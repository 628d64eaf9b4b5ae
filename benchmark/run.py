"""One run of one cell: ``python3 -m benchmark.run --workload <cell>
--seed <n> --seconds <s> --trace <0|1>``, from the root of a checkout.

The harness is driven by data.  It looks the cell up in
``BENCHMARK.json``, loads the configuration's file and
``traffic/<traffic>.json``, hands both to ``drivers/<kind>.py`` (the
traffic file names its ``kind``) and, in a traced run, applies the
reader ``layer_metrics/<name>.py`` of every per-layer metric the cell
has.  A new configuration, traffic mix, per-layer metric or cell is
new files and new entries; no file that exists needs an edit.

One process, which alone touches the chips.  It refuses to run on
anything but the number of TPU chips the cell names, keeps JAX's
compile cache where ``utils.enable_compile_cache`` puts it (inside the
checkout), and prints the contract's JSON object as the last line of
its standard output.
"""

from __future__ import annotations

import time

_T_PROCESS = time.monotonic()   # set-up is counted from here

import argparse      # noqa: E402
import importlib     # noqa: E402
import json          # noqa: E402
import sys           # noqa: E402
from pathlib import Path  # noqa: E402

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


class Refused(RuntimeError):
    """The run cannot give a result that would mean anything."""


# -- the cell, from data -----------------------------------------------------


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> dict:
    """Everything ``BENCHMARK.json`` and the files it names say about
    one cell.  A per-layer metric belongs to the cell when its entry
    admits the cell and the end-to-end metric it moves is reported
    there."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise Refused(f"no cell {name!r}; there are {sorted(cells)}")
    cell = cells[name]
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    bench_dir = root / bench["paths"][0]
    end_to_end = [m for m in bench["end_to_end"] if _applies(m, name)]
    moved = {m["name"] for m in end_to_end}
    return {
        "name": name,
        "chips": int(cell["chips"]),
        "config_name": cell["config"],
        "config": json.loads((root / entry["file"]).read_text()),
        "traffic_name": cell["traffic"],
        "traffic": json.loads(
            (bench_dir / "traffic" / f"{cell['traffic']}.json").read_text()
        ),
        "end_to_end": end_to_end,
        "per_layer": [
            m for m in bench["per_layer"]
            if _applies(m, name) and m["moves"] in moved
        ],
        "peaks_table": json.loads((bench_dir / "peaks.json").read_text()),
    }


def program_knobs(config: dict) -> dict:
    """The start of the dict a model class is built from: the file's
    ``program`` group and the architecture knobs it names out of the
    published keys (``program_from_published``: knob -> key)."""
    knobs = dict(config["program"])
    for knob, published in config.get("program_from_published", {}).items():
        knobs[knob] = config[published]
    return knobs


def _module(kind: str, name: str):
    """``drivers/<name>.py`` or ``layer_metrics/<name>.py`` of the
    benchmark package this file belongs to."""
    return importlib.import_module(f"{__package__}.{kind}.{name}")


# -- the device --------------------------------------------------------------


class CompileMeter:
    """JAX's own account of compilation (as ``chip_smoke.CompileMeter``):
    programs handed to the backend compiler or fetched from the
    persistent cache, and the seconds that took.  Listeners cannot be
    removed, so a process makes one meter and reads differences."""

    def __init__(self) -> None:
        import jax

        self.compile_s = 0.0
        self.programs = 0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._secs)
        jax.monitoring.register_event_listener(self._event)

    def _secs(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += duration
            self.programs += 1

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def read(self) -> dict:
        return {"compile_s": self.compile_s, "programs": self.programs,
                "cache_hits": self.hits, "cache_misses": self.misses}


def take_devices(cell: dict, rehearsal: bool) -> tuple[list, dict]:
    """The chips this process measures on, and the stamp every result
    carries.  Anything but the cell's number of TPU chips is refused —
    there is no fallback to the CPU.  ``rehearsal`` (tests only, never
    the command line) lets the control flow run elsewhere; its line
    then withholds every metric."""
    import jax

    devices = jax.devices()
    stamp = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    if rehearsal:
        return devices[:cell["chips"]], dict(stamp, count=cell["chips"])
    if stamp["platform"] != "tpu" or stamp["count"] != cell["chips"]:
        raise Refused(
            f"cell {cell['name']} needs {cell['chips']} TPU chip(s); this "
            f"process sees {stamp['count']} x {stamp['platform']} "
            f"({stamp['kind']})"
        )
    if stamp["kind"] not in cell["peaks_table"]:
        raise Refused(
            f"device kind {stamp['kind']!r} is not in peaks.json: add its "
            f"published peaks with their source"
        )
    return devices, stamp


def memory_peak_bytes(devices) -> int:
    """Peak bytes on the fullest chip, as the runtime reports it.  On
    this libtpu ``peak_bytes_in_use`` counts the buffers the process
    held (weights, optimizer state, staged data, cache pools) and
    ``peak_bytes_reserved`` what it set aside for the temporaries of
    the programs it ran (ResNet-50's step: 0.83 GB and 9.41 GB, the
    chip-less compile's 9.45 GB of temporaries; my chip run B, PR 23).
    The two peaks need not coincide, so their sum can overstate the
    true peak by what was held only before the first step (the
    float32 staging copy of the train set)."""
    def peak(d) -> int:
        stats = d.memory_stats() or {}
        return (stats.get("peak_bytes_in_use", 0)
                + stats.get("peak_bytes_reserved", 0))

    return int(max(peak(d) for d in devices))


# -- tracing -----------------------------------------------------------------


class Tracing:
    """The profiler around part of the window.  Host spans of the
    benchmark (``span(name)``) are ``jax.profiler.TraceAnnotation``s
    named ``bench:<name>``, so they land on the trace's own clock."""

    def __init__(self, directory: Path, enabled: bool,
                 recorded: Path | None = None):
        self.directory = directory
        self.enabled = enabled
        self.recorded = recorded    # rehearsals read a trace of the chip
        self.active = False
        self.overhead_s = 0.0    # seconds spent starting and stopping

    def start(self) -> None:
        if not self.enabled:
            return
        import shutil

        import jax

        t = time.monotonic()
        shutil.rmtree(self.directory, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0     # the host's Python frames
        options.host_tracer_level = 2       # are not read; TraceMes are
        jax.profiler.start_trace(str(self.directory), profiler_options=options)
        self.active = True
        self.overhead_s += time.monotonic() - t

    def stop(self) -> None:
        if not self.active:
            return
        import jax

        t = time.monotonic()
        jax.profiler.stop_trace()
        self.active = False
        self.overhead_s += time.monotonic() - t

    @staticmethod
    def span(name: str):
        import jax

        return jax.profiler.TraceAnnotation(f"bench:{name}")

    def load(self) -> dict | None:
        """The neutral trace (``trace_reduce``), or None untraced."""
        if not self.enabled:
            return None
        from . import trace_reduce

        if self.recorded is not None:
            return trace_reduce.load_recorded(str(self.recorded))
        return trace_reduce.load_xplane(
            trace_reduce.find_xplane(str(self.directory))
        )


# -- one run -----------------------------------------------------------------


def run_cell(name: str, *, seed: int, seconds: float, trace: bool,
             root: Path = ROOT, rehearsal: bool = False) -> dict:
    """Run the cell and return the result line as a dict."""
    cell = load_cell(name, root)
    driver = _module("drivers", cell["traffic"]["kind"])
    recorded = None
    if rehearsal:
        # tests only: the tiny sizes a file keeps beside the real ones
        cell["config"] = dict(cell["config"], **cell["config"]["rehearsal"])
        cell["traffic"] = dict(
            cell["traffic"], **cell["traffic"].get("rehearsal", {})
        )
        recorded = (BENCH_DIR / "recorded"
                    / cell["traffic"]["rehearsal"]["recorded_trace"])
    devices, stamp = take_devices(cell, rehearsal)

    from theanompi_tpu.utils import enable_compile_cache

    cache_dir = enable_compile_cache()
    scratch = root / ".bench_scratch" / name
    scratch.mkdir(parents=True, exist_ok=True)
    ctx = {
        "cell": cell,
        "seed": int(seed),
        "seconds": float(seconds),
        "devices": devices,
        "peaks": cell["peaks_table"].get(
            cell["traffic"]["rehearsal"]["recorded_device_kind"]
            if rehearsal else stamp["kind"]
        ),
        "meter": CompileMeter(),
        "tracing": Tracing(scratch / "trace", bool(trace), recorded),
        "t_process": _T_PROCESS,
        "scratch": scratch,
        "log": lambda **kw: print(json.dumps(kw), flush=True),
    }
    ctx["log"](event="start", cell=name, device=stamp, compile_cache=cache_dir,
               seed=seed, seconds=seconds, trace=bool(trace))
    out = driver.run(ctx)
    # out: correct, attempted, failed, end_to_end {name: value},
    #      memory_peak_bytes, facts (what the per-layer readers read)

    device = dict(stamp, memory_peak_bytes=out["memory_peak_bytes"])
    declared = cell["per_layer"] if trace else cell["end_to_end"]
    values: dict = {}
    line: dict = {"correct": bool(out["correct"]),
                  "attempted": int(out["attempted"]),
                  "failed": int(out["failed"])}
    if trace:
        from . import trace_reduce

        facts = dict(out["facts"], cell=cell, peaks=ctx["peaks"])
        summary = trace_reduce.summarize(facts["trace"])
        device.update(busy_s=summary["busy_s"], window_s=summary["window_s"])
        line["breakdown"] = {"device_ops": summary["device_ops"],
                             "idle_gaps": summary["idle_gaps"]}
        for m in declared:
            value = _module("layer_metrics", m["name"]).read(facts)
            if value is not None:       # nothing to read: left out
                values[m["name"]] = value
    else:
        values = {m["name"]: out["end_to_end"][m["name"]] for m in declared}
    units = {m["name"]: m["unit"] for m in declared}
    if rehearsal:
        # a CPU run never names a device metric
        line["metrics"] = {}
        line["withheld"] = sorted(values)
        line.pop("breakdown", None)
    else:
        line["metrics"] = {
            k: {"value": float(v), "unit": units[k]} for k, v in values.items()
        }
    line["device"] = device
    return line


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)
    try:
        line = run_cell(args.workload, seed=args.seed, seconds=args.seconds,
                        trace=bool(args.trace))
    except Refused as e:
        print(f"refused: {e}", file=sys.stderr)
        return 3
    sys.stdout.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
