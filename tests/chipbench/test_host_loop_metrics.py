"""The seven per-layer metrics that read the program's own account of
its host loop (ISSUE 25): each reader on hand-made events, None from a
program that lacks what it reads, and the traced rehearsal lines."""

import json

import pytest

from chipbench import device, run, spec

SERVING = ["engine_host_ms_per_step", "engine_stage_ms_per_step",
           "engine_fetch_wait_share", "engine_fetch_wait_max_ms",
           "host_pause_max_ms", "engine_warmup_s"]
NEW = SERVING + ["train_host_pause_max_ms"]
CHAT, BERT = "qwen2.5-3b-chat-sat", "bert-large-ft-s512"


def _observed(events: list):
    return device.Observed(
        cell=None, device_kind="cpu", chips=1, window_s=2.0, values={},
        counters={}, events=events, trace=None, trace_window_s=0.0,
        memory_peak_bytes=0, memory_limit_bytes=0, compiles_in_window=0)


def _ledger(iteration, dur, stage, dispatch, wait, commit, gap, slots):
    return {"type": "serve", "event": "iteration_ledger",
            "iteration": iteration, "dur_s": dur, "stage_s": stage,
            "dispatch_s": dispatch, "fetch_wait_s": wait,
            "commit_s": commit, "gap_s": gap, "decode_slots": slots}


def _span(name, mono, dur):
    return {"type": "span", "name": name, "mono": mono, "dur": dur,
            "tid": 1}


def _pause(mono, dur):
    return {"type": "host_pause", "mono": mono, "dur": dur}


METER = {"type": "metric", "name": "host/pause_max_s", "value": 1.5}

# three iterations: a prefill-only one (no decode), two that decoded
LEDGER = [_ledger(0, 0.500, 0.004, 0.010, 0.480, 0.002, 0.000, 0),
          _ledger(1, 0.060, 0.003, 0.002, 0.050, 0.001, 0.002, 16),
          _ledger(2, 0.058, 0.001, 0.002, 0.052, 0.001, 0.002, 16)]
# three engine iterations, 10.0-10.1, 10.2-10.3 and 10.4-10.5, after a
# warm-up that ran before telemetry had a directory
SPANS = [_span("serve/warmup", 1.0, 13.5),
         _span("serve/warmup/prefill_g4", 1.0, 5.0),
         _span("serve/step", 10.0, 0.1), _span("serve/step", 10.2, 0.1),
         _span("serve/step", 10.4, 0.1),
         _span("train/step_dispatch", 20.0, 0.01),
         _span("train/step_dispatch", 30.0, 0.01)]
# one pause between two iterations, two that reach into one, one inside
# the train loop and one after it
PAUSES = [_pause(10.11, 0.08), _pause(10.19, 0.03), _pause(10.45, 0.30),
          _pause(25.0, 0.045), _pause(30.5, 2.0)]


@pytest.mark.parametrize("name, events, want", [
    ("engine_host_ms_per_step", LEDGER, 8.0),     # median of 10 and 6
    ("engine_stage_ms_per_step", LEDGER, 2.0),    # median of 3 and 1
    ("engine_fetch_wait_share", LEDGER, 100 * 0.582 / 0.622),
    # three engines' runs (laps); the second's caller stopped the
    # profiler for 13 s between two iterations: the median lap's share
    ("engine_fetch_wait_share", LEDGER + [
        _ledger(0, 0.500, 0.004, 0.010, 0.480, 0.002, 0.000, 0),
        _ledger(1, 0.060, 0.003, 0.002, 0.050, 0.001, 13.0, 16),
        _ledger(0, 0.100, 0.004, 0.010, 0.075, 0.002, 0.000, 0)],
     75.0),         # of 93.6, 3.9 and 75.0
    ("engine_fetch_wait_max_ms", LEDGER, 480.0),
    ("host_pause_max_ms", SPANS + PAUSES + [METER], 300.0),
    ("host_pause_max_ms", SPANS + PAUSES[:1] + [METER], 0.0),
    ("train_host_pause_max_ms", SPANS + PAUSES + [METER], 45.0),
    ("train_host_pause_max_ms", SPANS + PAUSES[:3] + [METER], 0.0),
    ("engine_warmup_s", SPANS, 13.5),
])
def test_reader_on_hand_made_events(name, events, want):
    assert run.read_layer(name, _observed(events)) == pytest.approx(want)


OLD_LEDGER = [{k: v for k, v in e.items()
               if k in ("type", "event", "iteration", "dur_s",
                        "decode_slots")} for e in LEDGER]


@pytest.mark.parametrize("name", NEW)
@pytest.mark.parametrize("events", [
    [],
    # what the parent program writes: ledger lines without the account,
    # its own spans, no meter
    OLD_LEDGER + [_span("serve/decode_step", 10.0, 0.1),
                  _span("train/step_dispatch", 20.0, 0.01)],
], ids=["no-events", "parent-program"])
def test_reader_finds_nothing_and_does_not_raise(name, events):
    assert run.read_layer(name, _observed(events)) is None


def test_the_new_entries_end_the_list_and_name_their_cells():
    bench = spec.load_benchmark()
    tail = bench["per_layer"][-len(NEW):]
    assert [m["name"] for m in tail] == [
        "engine_host_ms_per_step", "engine_stage_ms_per_step",
        "engine_fetch_wait_share", "engine_fetch_wait_max_ms",
        "host_pause_max_ms", "train_host_pause_max_ms", "engine_warmup_s"]
    reports = {e["name"]: set(e.get("workloads")
                              or [w["name"] for w in bench["workloads"]])
               for e in bench["end_to_end"]}
    for m in tail:
        assert set(m["workloads"]) <= reports[m["moves"]], m["name"]
        assert m["workloads"] == ([BERT] if m["name"].startswith("train_")
                                  else [CHAT])


@pytest.fixture(scope="module")
def kept_chat_run(tmp_path_factory):
    """One traced rehearsal of chat-sat through the ledger tool: the
    run's own line, the tool's line, and the kept trace."""
    from chipbench.tools import host_loop_ledger

    keep = tmp_path_factory.mktemp("keep")
    with pytest.MonkeyPatch.context() as mp:
        lines = []
        mp.setattr("builtins.print",
                   lambda text, **_kw: lines.append(str(text)))
        assert host_loop_ledger.main(
            ["--workload", CHAT, "--seed", "8", "--seconds", "3",
             "--keep", str(keep)]) == 0
    return json.loads(lines[-2]), json.loads(lines[-1]), keep


def test_traced_chat_line_holds_the_six_serving_metrics(kept_chat_run):
    line, _, _ = kept_chat_run
    assert line["correct"] is True
    assert set(SERVING) <= set(line["metrics"])
    m = {k: line["metrics"][k]["value"] for k in SERVING}
    assert 0 < m["engine_stage_ms_per_step"] <= m["engine_host_ms_per_step"]
    assert 0 < m["engine_fetch_wait_share"] < 100
    assert m["engine_fetch_wait_max_ms"] > 0 and m["host_pause_max_ms"] >= 0
    assert m["engine_warmup_s"] > 0
    # every metric the cell had is still there
    assert {"compiles_in_window", "decode_step_device_ms",
            "prefill_busy_share", "prefill_dispatch_max_ms",
            "decode_batch_occupancy", "prefix_hit_share"} <= set(
                line["metrics"])


def test_ledger_tool_checks_the_identity_on_the_runs_events(kept_chat_run):
    _, check, _ = kept_chat_run
    assert check["ledger_lines"] > 20 and check["identity_broken_at"] == []
    assert 0.5 < check["accounted_share"] <= 1.0
    assert {"serve/warmup", "serve/warmup/prefill_g1",
            "serve/warmup/prefill_g4"} <= set(check["warmup_spans_s"])
    assert check["span_counts"]["serve/step"] == check["ledger_lines"]


def test_gaps_by_program_span_names_the_engines_spans(kept_chat_run):
    from chipbench import reduce
    from chipbench.tools import gaps_by_program_span as tool

    _, _, keep = kept_chat_run
    path = reduce.find_xplane(str(keep))
    spans = tool.host_spans(path)
    names = {sp[1] for sp in spans}
    assert {"hstd/serve/step", "hstd/serve/commit_fetch",
            "hstd/serve/stage_decode"} <= names
    trace = reduce.load_trace(path)
    rows = tool.idle_by_span(trace, spans)
    idle = sum(reduce.length([g]) for g in reduce.gaps(
        [(o.start_s, o.end_s) for o in trace.ops if o.device == 0]))
    assert sum(v for _, _, v in rows) == pytest.approx(idle)
    assert any(b == "chipbench/engine.step" and p.startswith("hstd/serve/")
               for b, p, _ in rows)
    own = {n: (c, t, s) for n, c, t, s in tool.own_and_self_seconds(spans)}
    count, total, self_s = own["hstd/serve/step"]
    assert count > 0 and 0 <= self_s < total
    assert "under a program span" in tool.report(str(keep))


def test_self_seconds_on_hand_made_spans():
    from chipbench.tools import gaps_by_program_span as tool

    spans = [(0, "hstd/a", 0.0, 10.0), (0, "hstd/b", 1.0, 4.0),
             (0, "hstd/c", 2.0, 3.0), (0, "hstd/b", 6.0, 7.0),
             (1, "hstd/a", 0.0, 2.0)]
    own = {n: (c, t, s) for n, c, t, s in tool.own_and_self_seconds(spans)}
    assert own["hstd/a"] == (2, pytest.approx(12.0), pytest.approx(8.0))
    assert own["hstd/b"] == (2, pytest.approx(4.0), pytest.approx(3.0))
    assert own["hstd/c"] == (1, pytest.approx(1.0), pytest.approx(1.0))


def test_traced_bert_line_holds_the_train_pause_metric(capsys):
    assert run.main(["--workload", BERT, "--seed", "9", "--seconds", "3",
                     "--trace", "1"]) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True
    assert line["metrics"]["train_host_pause_max_ms"]["value"] >= 0
    assert not set(SERVING) & set(line["metrics"])
