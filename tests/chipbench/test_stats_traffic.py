"""Percentile and lateness arithmetic, and the general traffic generator:
the same seed gives the same schedule, another seed another."""

import json
import os

import numpy as np
import pytest

from chipbench import loadgen, spec, stats


@pytest.mark.parametrize("q", [0, 25, 50, 90, 99, 100])
def test_percentile_matches_numpy(q):
    xs = list(np.random.default_rng(q).normal(size=137))
    assert stats.percentile(xs, q) == pytest.approx(np.percentile(xs, q))


def test_percentile_edges_and_spread():
    assert stats.percentile([], 90) is None
    assert stats.percentile([3.0], 99) == 3.0
    assert stats.median([1, 2, 3, 4]) == 2.5
    # quartiles of 1..5 are 2 and 4: spread (4 - 2) / 3
    assert stats.spread([1, 2, 3, 4, 5]) == pytest.approx(2 / 3)
    assert stats.spread([]) is None


def test_lateness_is_sent_minus_due_never_negative():
    late = stats.lateness([0.0, 1.0, 2.0], [0.001, 0.999, 2.010])
    assert late["n"] == 3
    assert late["max_ms"] == pytest.approx(10.0)
    assert late["p50_ms"] == pytest.approx(1.0)


def _traffic(name):
    if name == "open-docs":      # test data: an open-loop mix of sessions
        return spec.load_json(os.path.join(os.path.dirname(__file__),
                                           "data", name + ".json"))
    return spec.load_json(os.path.join(spec.HERE, "traffic", name + ".json"))


@pytest.mark.parametrize("name", ["chat-sat", "open-docs"])
def test_same_seed_same_schedule_other_seed_differs(name):
    t = dict(_traffic(name), schedule_seed=11)
    a = loadgen.make_requests(t, 7, 1000, 20.0)
    b = loadgen.make_requests(t, 7, 1000, 20.0)
    c = loadgen.make_requests(t, 8, 1000, 20.0)
    assert len(a) == len(b) > 0
    for x, y in zip(a, b):
        assert x.due_s == y.due_s and x.max_new_tokens == y.max_new_tokens
        assert np.array_equal(x.prompt, y.prompt)
    # with `schedule_seed` another seed gives other tokens on the same
    # arrivals and lengths
    assert [(x.due_s, len(x.prompt)) for x in a] == [
        (x.due_s, len(x.prompt)) for x in c]
    assert not np.array_equal(a[0].prompt, c[0].prompt)
    # without it the seed draws the schedule too
    free = {k: v for k, v in t.items() if k != "schedule_seed"}
    d = loadgen.make_requests(free, 7, 1000, 20.0)
    e = loadgen.make_requests(free, 8, 1000, 20.0)
    assert [len(x.prompt) for x in d[:20]] != [len(x.prompt) for x in e[:20]]


def test_open_loop_arrivals_cover_the_window_at_the_fixed_rate():
    t = _traffic("open-docs")
    rate = t["loop"]["rate_per_s"]
    plans = loadgen.make_requests(t, 3, 1000, 100.0)
    due = [p.due_s for p in plans]
    assert due == sorted(due) and 0 <= due[0] and due[-1] < 100.0
    assert len(plans) == pytest.approx(rate * 100.0, rel=0.1)


def test_sessions_share_a_document_and_interleave():
    t = _traffic("open-docs")
    plans = loadgen.make_requests(t, 5, 1000, 60.0)
    k, q = t["sessions"]["interleave"], t["sessions"]["questions"]
    by_session = {}
    for p in plans:
        by_session.setdefault(p.session, []).append(p)
    lo = t["sessions"]["shared_prefix_len"]["lo"]
    for members in by_session.values():
        assert len(members) <= q
        first = members[0].prompt
        for m in members[1:]:
            assert np.array_equal(m.prompt[:lo], first[:lo])
    # consecutive arrivals belong to different sessions
    assert all(plans[i].session != plans[i + 1].session
               for i in range(min(len(plans), 4 * k) - 1))
    hi = (t["sessions"]["shared_prefix_len"]["hi"] + t["prompt_len"]["hi"]
          + t["output_len"]["hi"])
    assert max(len(p.prompt) + p.max_new_tokens for p in plans) <= hi


def test_closed_loop_has_the_stated_number_of_requests_and_bounds():
    t = _traffic("chat-sat")
    plans = loadgen.make_requests(t, 1, 1000, 40.0)
    assert len(plans) == t["loop"]["requests"]
    lens = [len(p.prompt) for p in plans]
    assert min(lens) >= t["prompt_len"]["lo"]
    assert max(lens) <= t["prompt_len"]["hi"]
    assert 150 <= np.median(lens) <= 260          # median ~ 200
    outs = [p.max_new_tokens for p in plans]
    assert 64 <= min(outs) and max(outs) <= 512 and 120 <= np.median(outs) <= 190


@pytest.mark.parametrize("dist,lo,hi", [
    ({"dist": "fixed", "value": 9}, 9, 9),
    ({"dist": "uniform", "lo": 3, "hi": 5}, 3, 5),
    ({"dist": "pareto", "lo": 4, "hi": 64, "alpha": 1.5}, 4, 64),
    ({"dist": "lognormal", "lo": 8, "hi": 512, "median": 230, "sigma": 0.6},
     8, 512),
])
def test_length_distributions_stay_in_bounds(dist, lo, hi):
    import random

    rng = random.Random(0)
    xs = [loadgen.draw_length(dist, rng) for _ in range(500)]
    assert lo <= min(xs) and max(xs) <= hi


def test_the_chat_cell_holds_its_lengths_to_one_realisation():
    t = _traffic("chat-sat")
    assert "schedule_seed" in t and t["loop"]["kind"] == "closed"
    a = loadgen.make_requests(t, 1, 1000, 40.0)
    b = loadgen.make_requests(t, 2, 1000, 40.0)
    assert [(len(x.prompt), x.max_new_tokens) for x in a] == [
        (len(x.prompt), x.max_new_tokens) for x in b]
    # the realisation PR 22 measured: its first 64 requests
    assert sum(len(x.prompt) for x in a[:64]) == 24391
    assert sum(x.max_new_tokens for x in a[:64]) == 14121


def test_benchmark_json_names_files_that_exist():
    bench = spec.load_benchmark()
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    for c in bench["configs"]:
        assert os.path.exists(os.path.join(spec.ROOT, c["file"]))
    for w in bench["workloads"]:
        assert os.path.exists(os.path.join(
            spec.HERE, "traffic", w["traffic"] + ".json"))
        assert len(w["why"]) <= 200
    for m in bench["per_layer"]:
        assert os.path.exists(os.path.join(
            spec.HERE, "layers", m["name"] + ".py")), m["name"]
        # a per-layer metric is reported only where the metric it moves is
        for w in m.get("workloads", [x["name"] for x in bench["workloads"]]):
            reports = {e["name"] for e in bench["end_to_end"]
                       if "workloads" not in e or w in e["workloads"]}
            assert m["moves"] in reports, (m["name"], w)
    listed = {m["name"] + ".py" for m in bench["per_layer"]}
    assert listed == {f for f in os.listdir(os.path.join(spec.HERE, "layers"))
                      if f.endswith(".py") and f != "__init__.py"}
    four = [w for w in bench["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(bench["workloads"]) // 4)
    json.dumps(bench)
