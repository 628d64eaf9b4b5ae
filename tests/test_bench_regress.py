"""Bench-trajectory loader + regression gate (ISSUE 15 tentpole b:
``theanompi_tpu/obs/regress.py`` + ``scripts/bench_diff.py``).

The judged properties: every ``BENCH_*.json`` format the trajectory
ever accumulated round-trips through the loader (the older ones —
driver wrapper, truncated-tail salvage — as small fixtures of the same
shape, since the records that carried them are gone), the REAL
trajectory gates clean (r07→r08 included), a
synthetic trajectory with an injected 20% slowdown is FLAGGED while
the same move inside the row's own noise band is not, and the CLI's
``--gate`` exit codes follow.  Pure host-side logic, fast tier."""

import json
import subprocess
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from theanompi_tpu.obs import regress  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def _cap(name, rows):
    """A synthetic capture in the judge's normalized shape."""
    return {"name": name, "n": None, "format": "rows", "path": None,
            "rows": rows}


def _row(value, unit="images/sec/chip", spread=None, error=None):
    r = {"value": value, "unit": unit, "vs_baseline": None,
         "spread": spread, "metric": "m"}
    if error is not None:
        r["error"] = error
    return r


def _bench_row(metric, value, unit, **extra):
    return {"metric": metric, "value": value, "unit": unit,
            "vs_baseline": None, **extra}


@pytest.fixture()
def formats_dir(tmp_path):
    """One small capture per on-disk format, written as the driver
    and the container wrote them: the key/value baseline, the driver
    wrapper with its ``parsed`` record (r01), the wrapper whose record
    line out-grew the tail window — ``parsed`` null, head cut
    mid-row (r05) — and the in-container ``rows`` capture (r08)."""
    def write(name, obj):
        (tmp_path / f"BENCH_{name}.json").write_text(json.dumps(obj))

    write("BASELINE", {
        "_comment": "working baselines",
        "ResNet50_images_per_sec_per_chip": 2291.69,
        "ResNet50_config": "ResNet-50 v1.5 b128 bf16 BSP",
        "Llama_tokens_per_sec_per_chip": 49217.52,
    })
    headline = _bench_row(
        "ResNet50 images/sec/chip (BSP, bf16, b128)", 2195.31,
        "images/sec/chip", vs_baseline=0.9579,
    )
    write("r01", {
        "n": 1, "cmd": "python bench.py", "rc": 0,
        "tail": "WARNING: a log line\n" + json.dumps(headline) + "\n",
        "parsed": headline,
    })
    later_rows = {
        "llama": _bench_row(
            "Llama-8L-1024d tokens/sec/chip (BSP, bf16, b4, T2048)",
            77555.42, "tokens/sec/chip", spread=0.006,
        ),
        "alexnet": _bench_row(
            "AlexNet images/sec/chip (BSP, bf16, b128)", 9259.8,
            "images/sec/chip", spread=0.037,
        ),
        "loader": _bench_row(
            "native loader images/sec", 2900.0, "images/sec",
        ),
    }
    whole = json.dumps(later_rows)
    write("r05", {
        "n": 5, "cmd": "python bench.py", "rc": 0,
        # the head — record start, headline, the wresnet row's
        # opening — fell outside the driver's tail window
        "tail": 's_baseline": 1.0381, "spread": 0.0081, "mfu": 0.6025}, '
                + whole[1:] + "}\n",
        "parsed": None,
    })
    write("r08", {
        "n": 8, "platform": "cpu-container", "note": "self-capture",
        "rows": {"serving": _bench_row(
            "continuous-batching Llama serving tokens/sec", 1975.71,
            "tokens/sec",
        )},
    })
    return tmp_path


class TestLoaderRoundTrip:
    """Every capture in the repo, and one of every older format,
    parses — the legacy-format tolerance half of the ISSUE's test
    satellite."""

    def test_every_on_disk_capture_loads(self, formats_dir):
        paths = sorted(ROOT.glob("BENCH_*.json")) + sorted(
            formats_dir.glob("BENCH_*.json")
        )
        assert len(paths) >= 9   # BASELINE, r04, r06.. + the fixtures
        for p in paths:
            cap = regress.load_capture(p)
            assert cap is not None, p.name
            assert cap["rows"], f"{p.name} yielded no rows"
            for row in cap["rows"].values():
                assert "value" in row

    def test_format_detection(self, formats_dir):
        by_name = {
            c["name"]: c for c in regress.load_history(formats_dir)
        }
        assert by_name["BASELINE"]["format"] == "baseline-kv"
        assert by_name["r01"]["format"] == "wrapper"
        assert by_name["r05"]["format"] == "tail-salvage"
        assert by_name["r08"]["format"] == "rows"
        # and what is still on disk: the driver's last surviving
        # wrapper and the container captures
        on_disk = {c["name"]: c for c in regress.load_history(ROOT)}
        assert on_disk["BASELINE"]["format"] == "baseline-kv"
        assert on_disk["r04"]["format"] == "wrapper"
        assert on_disk["r08"]["format"] == "rows"

    def test_r05_tail_salvage_recovers_rows(self, formats_dir):
        """A capture that predates BENCH_HEADLINE and whose record
        line was cut at the head — the later rows still parse whole
        from the tail."""
        cap = regress.load_capture(formats_dir / "BENCH_r05.json")
        assert {"llama", "alexnet", "loader"} <= set(cap["rows"])
        assert cap["rows"]["llama"]["value"] == 77555.42

    def test_trajectory_order(self):
        names = [c["name"] for c in regress.load_history(ROOT)]
        assert names[0] == "BASELINE"
        assert names[1:] == sorted(
            names[1:], key=lambda n: int(n[1:])
        )

    def test_headline_line_preferred_when_present(self, tmp_path):
        """A truncated capture whose tail still holds the
        BENCH_HEADLINE last line salvages from IT — value AND
        secondary rows survive any head cut (why bench.py prints
        it)."""
        headline = {
            "metric": "ResNet50 images/sec/chip (BSP)", "value": 100.0,
            "unit": "images/sec/chip", "vs_baseline": 1.0,
            "secondary": {"llama": {"value": 5.0, "vs_baseline": 1.1}},
        }
        tail = ('...head was cut..."}}\n'
                "BENCH_HEADLINE " + json.dumps(headline) + "\n")
        p = tmp_path / "BENCH_r99.json"
        p.write_text(json.dumps(
            {"n": 99, "cmd": "x", "rc": 0, "tail": tail, "parsed": None}
        ))
        cap = regress.load_capture(p)
        assert cap["format"] == "tail-salvage"
        assert cap["rows"]["resnet50"]["value"] == 100.0
        assert cap["rows"]["llama"]["value"] == 5.0

    def test_salvaged_headline_keeps_verdict_direction(self, tmp_path):
        """The compact headline carries each row's UNIT, so a
        lower-better row salvaged from a truncated capture still
        regresses UPWARD — unit-less it would read a 50% slowdown as
        'improved' (review finding)."""
        from bench import _headline_line

        hist = [_cap("r00", {"gosgd": _row(10.0, unit="ms/round",
                                           spread=0.02)}),
                _cap("r01", {"gosgd": _row(10.1, unit="ms/round",
                                           spread=0.02)})]
        rec = {"metric": "x", "value": None, "unit": None,
               "secondary": {"gosgd": {
                   "value": 15.0, "unit": "ms/round", "spread": 0.02,
                   "metric": "m"}}}
        line = _headline_line(rec)
        tail = "BENCH_HEADLINE " + line[len("BENCH_HEADLINE "):] + "\n"
        p = tmp_path / "BENCH_r02.json"
        p.write_text(json.dumps(
            {"n": 2, "cmd": "x", "rc": 0, "tail": tail, "parsed": None}
        ))
        cap = regress.load_capture(p)
        assert cap["rows"]["gosgd"]["unit"] == "ms/round"
        j = regress.judge_capture(hist, cap)
        assert j["rows"]["gosgd"]["verdict"] == "regressed"

    def test_malformed_file_is_skipped_not_fatal(self, tmp_path):
        (tmp_path / "BENCH_r01.json").write_text("{not json")
        assert regress.load_capture(tmp_path / "BENCH_r01.json") is None
        assert regress.load_history(tmp_path) == []


class TestRealTrajectoryGatesClean:
    def test_r08_vs_r07_clean(self):
        """THE acceptance bar: the real BENCH_BASELINE..r08 trajectory
        exits 0 — including the CPU-container serving rows, whose
        ~30% accepted r06→r07 swing the trajectory band absorbs."""
        history = regress.load_history(ROOT)
        j = regress.judge_capture(history)
        assert j["capture"] == history[-1]["name"]
        assert j["verdict"] == "ok", j["rows"]
        assert j["regressed"] == []
        # the serving rows were actually judged, not skipped —
        # truncate to the newest capture CARRYING them (later
        # captures may be partial, e.g. the r10 loader-only capture)
        while history and "serving" not in history[-1]["rows"]:
            history.pop()
        js = regress.judge_capture(history)
        assert js["verdict"] == "ok", js["rows"]
        judged = {
            n for n, v in js["rows"].items()
            if v["verdict"] in ("ok", "improved")
        }
        assert {"serving", "serving_paged", "serving_fleet",
                "serving_autoscale"} <= judged

    def test_rows_missing_from_newest_never_gate(self):
        j = regress.judge_capture(regress.load_history(ROOT))
        assert j["rows"]["resnet50"]["verdict"] == "absent"


class TestSyntheticVerdicts:
    def _history(self, values, spread=0.02, unit="images/sec/chip"):
        return [
            _cap(f"r{i:02d}", {"row": _row(v, unit=unit,
                                           spread=spread)})
            for i, v in enumerate(values)
        ]

    def test_injected_20pct_slowdown_flagged(self):
        """The ISSUE's noise-handling bar: a stable trajectory
        (spread 2%) followed by a 20% slowdown is a confirmed
        regression."""
        hist = self._history([100.0, 101.0, 99.5, 100.5])
        j = regress.judge_capture(
            hist, _cap("r99", {"row": _row(80.0, spread=0.02)})
        )
        assert j["verdict"] == "regressed"
        assert j["regressed"] == ["row"]
        assert j["rows"]["row"]["ratio"] == 0.7960

    def test_slowdown_inside_band_passes(self):
        hist = self._history([100.0, 101.0, 99.5])
        j = regress.judge_capture(
            hist, _cap("r99", {"row": _row(95.0, spread=0.02)})
        )
        assert j["verdict"] == "ok"          # 5% < the 8% floor

    def test_improvement_beyond_band_reported(self):
        hist = self._history([100.0, 100.5])
        j = regress.judge_capture(
            hist, _cap("r99", {"row": _row(130.0, spread=0.02)})
        )
        assert j["rows"]["row"]["verdict"] == "improved"
        assert j["verdict"] == "ok"          # improvements never gate

    def test_accepted_improvements_are_not_noise(self):
        """A row with a big ACCEPTED win must stay guardable: the
        trajectory band learns from adverse excursions only, so a
        2.1x improvement followed by a -48% collapse is a confirmed
        regression (review finding — a |ratio-1| band of 1.1 read it
        as 'ok')."""
        hist = self._history([100.0, 210.0], spread=0.02)
        j = regress.judge_capture(
            hist, _cap("r99", {"row": _row(110.0, spread=0.02)})
        )
        v = j["rows"]["row"]
        assert v["verdict"] == "regressed", v
        assert v["band"] < 0.2

    def test_noisy_history_widens_the_band(self):
        """A row whose ACCEPTED trajectory already swung 30% (the
        CPU-container serving rows) must not flag on a 25% move —
        the band is learned from the row's own history."""
        hist = self._history([100.0, 70.0, 95.0], spread=None)
        j = regress.judge_capture(
            hist, _cap("r99", {"row": _row(72.0)})
        )
        v = j["rows"]["row"]
        assert v["band"] >= 0.30
        assert v["verdict"] == "ok"

    def test_recorded_spread_widens_the_band(self):
        hist = self._history([100.0, 100.0], spread=0.25)
        j = regress.judge_capture(
            hist, _cap("r99", {"row": _row(80.0, spread=0.25)})
        )
        assert j["rows"]["row"]["verdict"] == "ok"

    def test_lower_better_units_flag_increases(self):
        """wait_frac / ms-per-round rows regress UPWARD."""
        hist = self._history([10.0, 10.1], unit="ms/round")
        j = regress.judge_capture(
            hist, _cap("r99", {"row": _row(13.0, unit="ms/round",
                                           spread=0.02)})
        )
        assert j["rows"]["row"]["verdict"] == "regressed"
        j2 = regress.judge_capture(
            hist, _cap("r99", {"row": _row(8.0, unit="ms/round",
                                           spread=0.02)})
        )
        assert j2["rows"]["row"]["verdict"] == "improved"

    def test_platform_boundary_judges_as_new(self):
        """A row that declares a platform never compares against a
        different (or undeclared) platform's values: the r05 native
        loader ran on the chip-attached host at ~2900 img/s, the
        cpu-container capture reads ~1650 — two machines, not a 43%
        regression.  A platform-less row (legacy captures, the
        in-flight record) stays wildcard and compares as before."""
        chip = _row(2900.0, unit="images/sec", spread=0.02)
        cont = dict(_row(1650.0, unit="images/sec", spread=0.02),
                    platform="cpu-container")
        j = regress.judge_capture(
            [_cap("r05", {"row": chip})], _cap("r10", {"row": cont})
        )
        assert j["rows"]["row"]["verdict"] == "new"
        # same declared platform on both sides: judged normally
        prev = dict(chip, platform="cpu-container")
        j2 = regress.judge_capture(
            [_cap("r09", {"row": prev})], _cap("r10", {"row": cont})
        )
        assert j2["rows"]["row"]["verdict"] == "regressed"
        # wildcard current row (no platform) compares against anything
        j3 = regress.judge_capture(
            [_cap("r09", {"row": prev})],
            _cap("r10", {"row": _row(1650.0, unit="images/sec",
                                     spread=0.02)})
        )
        assert j3["rows"]["row"]["verdict"] == "regressed"
        # and the band learned from history skips the cross-platform
        # jump (a machine change is not accepted noise)
        hist = [_cap("r04", {"row": _row(5000.0, unit="images/sec")}),
                _cap("r05", {"row": chip}),
                _cap("r09", {"row": prev})]
        j4 = regress.judge_capture(
            hist, _cap("r10", {"row": dict(cont, value=2800.0)})
        )
        v = j4["rows"]["row"]
        assert v["vs"] == "r09"
        assert v["band"] == regress.BAND_FLOOR

    def test_new_row_never_gates(self):
        hist = self._history([100.0])
        j = regress.judge_capture(
            hist, _cap("r99", {"row": _row(100.0),
                               "fresh": _row(5.0)})
        )
        assert j["rows"]["fresh"]["verdict"] == "new"
        assert j["verdict"] == "ok"

    def test_errored_row_reported_not_gated(self):
        hist = self._history([100.0, 100.0])
        j = regress.judge_capture(
            hist, _cap("r99", {"row": _row(None, error="boom")})
        )
        assert j["rows"]["row"]["verdict"] == "error"
        assert j["verdict"] == "ok"

    def test_error_capture_skipped_as_comparison_base(self):
        """A capture that ERRORED a row must not become the prev
        value (nor poison the trajectory band)."""
        hist = self._history([100.0, 101.0])
        hist.append(_cap("r90", {"row": _row(None, error="infra")}))
        j = regress.judge_capture(
            hist, _cap("r99", {"row": _row(100.5, spread=0.02)})
        )
        v = j["rows"]["row"]
        assert v["vs"] == "r01" and v["verdict"] == "ok"


class TestJudgeRecord:
    def test_compact_self_judgment(self):
        rec = {"metric": "ResNet50 images/sec/chip (BSP)",
               "value": 2300.0, "unit": "images/sec/chip",
               "secondary": {
                   "serving": {"value": 1900.0, "unit": "tokens/sec"},
               }}
        out = regress.judge_record(rec, ROOT)
        assert out["verdict"] in ("ok", "regressed")
        assert "regressed" in out

    def test_never_raises_on_broken_history(self, tmp_path):
        out = regress.judge_record({"value": 1.0}, tmp_path)
        assert out["verdict"] in ("ok", "unknown")


class TestHeadlineRegressField:
    def test_headline_line_carries_regress(self):
        from bench import _headline_line

        rec = {"metric": "ResNet50 images/sec/chip (BSP)",
               "value": 2300.0, "unit": "images/sec/chip",
               "vs_baseline": 1.0}
        line = _headline_line(rec)
        assert line.startswith("BENCH_HEADLINE ")
        compact = json.loads(line[len("BENCH_HEADLINE "):])
        assert compact["regress"]["verdict"] in (
            "ok", "regressed", "unknown"
        )

    def test_headline_regress_flags_a_slowdown(self):
        """The self-judging capture: a record 40% under the newest
        on-disk serving capture reports itself regressed."""
        from bench import _headline_line

        # newest capture CARRYING a serving row (later captures may
        # be partial — r10 carries only the loader row)
        newest = [c for c in regress.load_history(ROOT)
                  if "serving" in c["rows"]][-1]
        prev = newest["rows"]["serving"]["value"]
        rec = {"metric": "x", "value": None, "unit": None,
               "secondary": {"serving": {
                   "value": prev * 0.5, "unit": "tokens/sec"}}}
        line = _headline_line(rec)
        compact = json.loads(line[len("BENCH_HEADLINE "):])
        assert compact["regress"]["verdict"] == "regressed"
        assert "serving" in compact["regress"]["regressed"]


class TestBenchDiffCLI:
    def _run(self, *args):
        return subprocess.run(
            [sys.executable, str(ROOT / "scripts" / "bench_diff.py"),
             *args],
            capture_output=True, text=True, timeout=120,
        )

    def test_gate_green_over_real_trajectory(self):
        r = self._run("--gate")
        assert r.returncode == 0, r.stdout + r.stderr

    def test_table_mode(self):
        r = self._run()
        assert r.returncode == 0
        assert "serving" in r.stdout and "verdict" in r.stdout

    def test_gate_red_on_injected_regression(self, tmp_path):
        """A fixture trajectory with a 20% slowdown outside the
        recorded spread exits nonzero — the ISSUE acceptance bar."""
        for i, v in enumerate([100.0, 101.0, 100.2]):
            (tmp_path / f"BENCH_r{i:02d}.json").write_text(json.dumps({
                "n": i, "platform": "x",
                "rows": {"resnet50": {
                    "metric": "m", "value": v,
                    "unit": "images/sec/chip", "spread": 0.02}},
            }))
        (tmp_path / "BENCH_r03.json").write_text(json.dumps({
            "n": 3, "platform": "x",
            "rows": {"resnet50": {
                "metric": "m", "value": 80.0,
                "unit": "images/sec/chip", "spread": 0.02}},
        }))
        r = self._run("--repo", str(tmp_path), "--gate")
        assert r.returncode == 1, r.stdout + r.stderr
        assert "REGRESSED" in r.stderr

    def test_capture_file_mode(self, tmp_path):
        rec = {"metric": "ResNet50 images/sec/chip", "value": 2300.0,
               "unit": "images/sec/chip"}
        p = tmp_path / "rec.json"
        p.write_text(json.dumps(rec))
        r = self._run("--capture", str(p), "--json")
        assert r.returncode == 0, r.stdout + r.stderr
        out = json.loads(r.stdout)
        assert out["capture"] == "rec"

    def test_empty_repo_exits_2(self, tmp_path):
        r = self._run("--repo", str(tmp_path))
        assert r.returncode == 2
