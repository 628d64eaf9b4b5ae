"""The open-loop generator: deterministic for a seed, inside its
clips, on schedule, and honest about how late it ran."""

import time

from benchmark import loadgen

#: the mix ISSUE 23 names for a chat cell (no file of the benchmark
#: holds it yet: PERF.md, Open questions)
TRAFFIC = {
    "kind": "open_loop",
    "rate_rps": 3.0,
    "prompt_tokens": {"median": 512, "sigma": 0.8, "min": 64, "max": 3072},
    "output_tokens": {"median": 128, "sigma": 0.6, "min": 16, "max": 512},
    "max_total_tokens": 4096,
}


def _plan(seed, **over):
    return loadgen.plan(dict(TRAFFIC, **over), seed=seed, seconds=40.0,
                        vocab=32000)


def test_same_seed_same_plan_other_seed_other_plan():
    a, b, c = _plan(7), _plan(7), _plan(8)
    assert a == b
    assert [p.due_s for p in a] != [p.due_s for p in c]
    assert [len(p.prompt) for p in a] != [len(p.prompt) for p in c]
    assert [p.prompt for p in a] != [p.prompt for p in c]


def test_plan_respects_the_file():
    plan = _plan(1)
    assert 80 <= len(plan) <= 160                 # Poisson, mean 120
    assert all(0 <= p.due_s < 40.0 for p in plan)
    assert [p.due_s for p in plan] == sorted(p.due_s for p in plan)
    lens = [len(p.prompt) for p in plan]
    spec_p, spec_o = TRAFFIC["prompt_tokens"], TRAFFIC["output_tokens"]
    assert min(lens) >= spec_p["min"] and max(lens) <= spec_p["max"]
    assert all(spec_o["min"] <= p.max_tokens <= spec_o["max"] for p in plan)
    assert all(len(p.prompt) + p.max_tokens <= TRAFFIC["max_total_tokens"]
               for p in plan)
    assert all(1 <= t < 32000 for p in plan for t in p.prompt)
    median = sorted(lens)[len(lens) // 2]
    assert 0.7 * spec_p["median"] <= median <= 1.45 * spec_p["median"]


def test_poisson_gaps_have_the_files_rate():
    gaps = []
    for seed in range(8):
        due = [p.due_s for p in _plan(seed)]
        gaps += [b - a for a, b in zip(due, due[1:])]
    mean = sum(gaps) / len(gaps)
    cv = (sum((g - mean) ** 2 for g in gaps) / len(gaps)) ** 0.5 / mean
    assert 0.9 / 3.0 <= mean <= 1.1 / 3.0
    assert 0.85 <= cv <= 1.15                     # exponential: CV 1


def test_a_total_that_leaves_no_output_is_refused():
    import pytest

    with pytest.raises(ValueError):
        _plan(1, max_total_tokens=64)


def test_open_loop_submits_on_schedule_and_reports_lateness():
    plan = [loadgen.Planned(0.00, [1], 1), loadgen.Planned(0.05, [2], 1),
            loadgen.Planned(0.10, [3], 1)]
    seen = []

    def slow_submit(p):          # the second submission stalls the thread
        seen.append((p.prompt[0], time.monotonic()))
        if p.prompt[0] == 2:
            time.sleep(0.2)
        return p.prompt[0]

    gen = loadgen.OpenLoop(plan, slow_submit)
    t0 = gen.start()
    assert gen.join(5.0)
    assert [s[1] for s in gen.sent] == [1, 2, 3]
    assert seen[1][1] - t0 >= 0.05                # never early
    late = [s[2] for s in gen.sent]
    assert late[0] < 0.05 and late[1] < 0.05
    assert late[2] >= 0.1                         # due at 0.10, sent ~0.25
    report = gen.lateness()
    assert report["n"] == 3 and report["max_ms"] >= 100
