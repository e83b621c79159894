"""Request-lifecycle tracing (ISSUE 10): the tier-1 decomposition gate
— a REAL engine run's ``request_timeline`` events must decompose each
request's e2e into queue + prefill + decode + preempted + overhead
within tolerance, with the accounting entirely host-side
(``test_serve_gates.py::test_no_compile_after_warmup`` runs with the
timeline on, so zero new compiled variants is enforced there) — plus the jax-less
``obs/timeline.py`` tooling: sliding-window percentile estimator,
incremental tail follower (never re-reads the prefix), deterministic
``obsctl timeline|slo`` output, and the poisoned-jax import contract
extended over all of it.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from huggingface_sagemaker_tensorflow_distributed_tpu import obs
from huggingface_sagemaker_tensorflow_distributed_tpu.obs.timeline import (
    SlidingWindow,
    TailFollower,
    TailStats,
    check_decomposition,
    chrome_trace,
    collect_timelines,
    gantt_text,
    slo_attribution,
)

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_OBSCTL = os.path.join(_REPO, "scripts", "obsctl.py")


# -- synthetic records (pure host, no jax) ------------------------------------

def _tl_event(rid, t=1000.0, at="finish", group="", q=0.3, pf=0.1,
              dc=0.5, pe=0.0, oh=0.1, bucket=64, **extra):
    """One schema-valid request_timeline event whose segments agree
    with its aggregates by construction."""
    e2e = q + pf + dc + pe + oh
    segs = [{"ph": "queue", "t0": 0.0, "dur": q}]
    cursor = q
    if pe:
        segs.append({"ph": "preempted", "t0": cursor, "dur": pe})
        cursor += pe
    segs.append({"ph": "prefill", "t0": cursor, "dur": pf,
                 "from": 0, "chunks": 1})
    cursor += pf
    segs.append({"ph": "decode", "t0": cursor + oh, "dur": dc,
                 "bucket": bucket, "iters": 10, "tokens": 10})
    ev = {"v": 1, "t": t, "host": 0, "pid": 1, "type": "serve",
          "event": "request_timeline", "request": rid, "at": at,
          "e2e_s": round(e2e, 6), "queue_s": q, "prefill_s": pf,
          "decode_s": dc, "preempted_s": pe, "overhead_s": round(oh, 6),
          "tokens": 10, "prompt_len": 5, "preemptions": 1 if pe else 0,
          "segments": segs, "ttft_s": round(q + pf, 6)}
    if group:
        ev["group"] = group
    ev.update(extra)
    return ev


def _ledger_event(i, t=1000.0, tokens=4, dur=0.05, waiting=2,
                  kv=0.5):
    return {"v": 1, "t": t, "host": 0, "pid": 1, "type": "serve",
            "event": "iteration_ledger", "iteration": i,
            "dur_s": dur, "prefill_s": 0.01, "decode_s": 0.03,
            "gather_bucket": 64, "prefill_chunks": 1,
            "prefill_dispatches": 1, "decode_slots": 3,
            "tokens": tokens, "waiting": waiting, "kv_used_frac": kv}


def _write_events(path, events):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w", encoding="utf-8") as f:
        for e in events:
            f.write(json.dumps(e) + "\n")


# -- sliding-window estimator -------------------------------------------------

def test_sliding_window_percentile_exact_and_evicting():
    from huggingface_sagemaker_tensorflow_distributed_tpu.obs.report import (
        percentile,
    )

    win = SlidingWindow(5)
    assert win.percentile(0.5) is None and win.mean() is None
    vals = [5.0, 1.0, 9.0, 3.0, 7.0, 2.0, 8.0]
    for i, v in enumerate(vals):
        win.push(v)
        expect = sorted(vals[max(0, i - 4):i + 1])
        # exact nearest-rank over the CURRENT window, same convention
        # as obs.report.percentile — no sketch error anywhere
        for p in (0.0, 0.5, 0.95, 0.99, 1.0):
            assert win.percentile(p) == percentile(expect, p)
    assert len(win) == 5
    assert win.sum() == pytest.approx(sum(vals[-5:]))
    # duplicates evict correctly (bisect_left removes ONE copy)
    dup = SlidingWindow(3)
    for v in (2.0, 2.0, 2.0, 4.0):
        dup.push(v)
    assert len(dup) == 3 and dup.percentile(1.0) == 4.0
    with pytest.raises(ValueError):
        SlidingWindow(0)


# -- tail follower ------------------------------------------------------------

def test_tail_follower_reads_appends_only(tmp_path):
    path = str(tmp_path / "events.jsonl")
    e1, e2, e3 = (_ledger_event(i, t=1000.0 + i) for i in range(3))
    _write_events(path, [e1])
    fol = TailFollower(path)
    events, errors = fol.poll()
    assert not errors and [e["iteration"] for e in events] == [0]
    # nothing new: empty poll
    assert fol.poll() == ([], [])
    # append one complete + one PARTIAL line: only the complete one is
    # consumed; the partial stays unconsumed until its newline lands
    with open(path, "a", encoding="utf-8") as f:
        f.write(json.dumps(e2) + "\n")
        f.write(json.dumps(e3)[:20])
    events, errors = fol.poll()
    assert not errors and [e["iteration"] for e in events] == [1]
    with open(path, "a", encoding="utf-8") as f:
        f.write(json.dumps(e3)[20:] + "\n")
    events, errors = fol.poll()
    assert not errors and [e["iteration"] for e in events] == [2]


def test_tail_follower_never_rereads_prefix(tmp_path):
    """The incremental contract, observable: after a poll, clobber the
    already-consumed prefix bytes in place — if the follower ever
    seeks back it would now see garbage, so a clean second poll PROVES
    the prefix is not re-read."""
    path = str(tmp_path / "events.jsonl")
    _write_events(path, [_ledger_event(0)])
    fol = TailFollower(path)
    events, errors = fol.poll()
    assert not errors and len(events) == 1
    size = os.path.getsize(path)
    with open(path, "r+b") as f:
        f.write(b"x" * (size - 1))       # torch the consumed prefix
    with open(path, "a", encoding="utf-8") as f:
        f.write(json.dumps(_ledger_event(1)) + "\n")
    events, errors = fol.poll()
    assert not errors and [e["iteration"] for e in events] == [1]


def test_tail_follower_flags_truncation(tmp_path):
    """A recreated/truncated file below the consumed offset must fail
    loud — silence would read as an idle engine forever."""
    path = str(tmp_path / "events.jsonl")
    _write_events(path, [_ledger_event(0), _ledger_event(1)])
    fol = TailFollower(path)
    events, errors = fol.poll()
    assert not errors and len(events) == 2
    _write_events(path, [_ledger_event(2)])      # recreated, shorter
    events, errors = fol.poll()
    assert not events and errors
    assert "truncated" in errors[0]


def test_tail_follower_flags_malformed_complete_lines(tmp_path):
    path = str(tmp_path / "events.jsonl")
    _write_events(path, [_ledger_event(0)])
    with open(path, "a", encoding="utf-8") as f:
        f.write('{"not json\n')
    fol = TailFollower(path)
    events, errors = fol.poll()
    assert len(events) == 1 and errors
    assert "unparseable" in errors[0]


def test_tail_stats_rolls_ledger_and_ttft():
    stats = TailStats(window=4)
    for i in range(6):
        stats.update(_ledger_event(i, tokens=4, dur=0.5, waiting=i,
                                   kv=0.1 * i))
    first = {"v": 1, "t": 1.0, "host": 0, "pid": 1, "type": "serve",
             "event": "first_token", "request": 0, "ttft_s": 0.25}
    stats.update(first)
    assert stats.waiting == 5 and stats.iteration == 5
    assert stats.kv_used_frac == pytest.approx(0.5)
    line = stats.render()
    # windowed tokens/sec: 4 ledgers * 4 tokens / (4 * 0.5s) = 8.0
    assert "tok/s=8.0" in line and "ttft_p50_s=0.25" in line


def test_tail_stats_rolling_slo_attainment():
    """ISSUE 16: verdict-carrying finish events roll a windowed
    attainment column into the tail line; closed-loop streams (no
    verdicts) keep their exact pre-16 rendering — the column is
    absent, not 'slo_attainment=-'."""
    def _finish(rid, met):
        return {"v": 1, "t": 1000.0 + rid, "host": 0, "pid": 1,
                "type": "serve", "event": "finish", "request": rid,
                "tokens": 4, "preemptions": 0, "slo_met": met}

    closed = TailStats(window=4)
    closed.update(_ledger_event(0))
    assert "slo_attainment" not in closed.render()
    # a finish WITHOUT a verdict (closed-loop) keeps the column absent
    no_verdict = _finish(1, True)
    del no_verdict["slo_met"]
    closed.update(no_verdict)
    assert "slo_attainment" not in closed.render()
    # a mistyped verdict is ignored, not crashed on or miscounted
    closed.update({**_finish(2, True), "slo_met": "yes"})
    assert "slo_attainment" not in closed.render()

    stats = TailStats(window=4)
    for rid, met in enumerate([True, True, False, True]):
        stats.update(_finish(rid, met))
    assert "slo_attainment=0.750" in stats.render()
    # the window ROLLS: four more hits evict the miss entirely
    for rid in range(4, 8):
        stats.update(_finish(rid, True))
    assert "slo_attainment=1.000" in stats.render()


def test_cli_tail_renders_attainment_column(tmp_path):
    """The live view of the same column: one poll over a stream whose
    finishes carry verdicts prints it, rc 0."""
    path = str(tmp_path / "events.jsonl")
    _write_events(path, [
        _ledger_event(0),
        {"v": 1, "t": 1001.0, "host": 0, "pid": 1, "type": "serve",
         "event": "finish", "request": 0, "tokens": 4,
         "preemptions": 0, "slo_met": True},
        {"v": 1, "t": 1002.0, "host": 0, "pid": 1, "type": "serve",
         "event": "finish", "request": 1, "tokens": 4,
         "preemptions": 0, "slo_met": False},
    ])
    proc = subprocess.run(
        [sys.executable, _OBSCTL, "tail", path, "--updates", "1",
         "--interval", "0.05"],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        cwd=_REPO)
    assert proc.returncode == 0, proc.stderr
    assert "slo_attainment=0.500" in proc.stdout


# -- decomposition checker / attribution over synthetic records ---------------

def test_check_decomposition_accepts_consistent_and_names_bugs():
    good = _tl_event(0)
    assert check_decomposition(good) == []
    # a double-attributed dispatch: decode_s inflated past what e2e
    # can hold -> negative overhead -> phase sum breaks
    bad = _tl_event(1, dc=5.0, oh=0.1)
    bad["e2e_s"] = 1.0
    bad["overhead_s"] = round(1.0 - (0.3 + 0.1 + 5.0), 6)
    assert any("negative overhead" in e or "phase sum" in e
               or "outside" in e for e in check_decomposition(bad))
    # segments disagreeing with the aggregates
    drift = _tl_event(2)
    drift["segments"][-1]["dur"] = 0.01
    assert any("decode segments sum" in e
               for e in check_decomposition(drift))
    # mistyped field
    broken = _tl_event(3)
    broken["queue_s"] = None
    assert check_decomposition(broken)


def test_collect_timelines_keys_by_process_and_request():
    """Request ids are per-process counters: a multi-host merge AND a
    same-host restart (two runs appended into one events.jsonl — two
    os pids, both host 0) must keep each process's rid 0 as a DISTINCT
    record; the Chrome trace separates processes as viewer-pid rows."""
    a = _tl_event(0, t=1000.0, group="h0")
    b = _tl_event(0, t=1001.0, group="h1")
    b["host"] = 1
    c = _tl_event(0, t=1002.0, group="h0-run2")
    c["pid"] = 2                         # same host, restarted process
    recs = collect_timelines([a, b, c])
    assert len(recs) == 3
    assert [(r.get("host", 0), r["pid"], r["request"])
            for r in recs] == [(0, 1, 0), (0, 2, 0), (1, 1, 0)]
    doc = chrome_trace(recs)
    assert {(e["pid"], e["tid"]) for e in doc["traceEvents"]} == \
        {(0, 0), (1, 0), (2, 0)}         # 3 distinct viewer rows
    assert all(e["args"]["host"] in (0, 1)
               for e in doc["traceEvents"])
    text = gantt_text(recs)
    assert "h0:p1:r0" in text and "h0:p2:r0" in text \
        and "h1:p1:r0" in text


def test_collect_timelines_last_event_wins_any_order():
    pre = _tl_event(7, t=1000.0, at="preempt", dc=0.0, pe=0.0)
    fin = _tl_event(7, t=1002.0, at="finish", pe=0.2)
    other = _tl_event(3, t=1001.0)
    for order in ([pre, fin, other], [fin, other, pre],
                  [other, pre, fin]):
        recs = collect_timelines(order)
        assert [r["request"] for r in recs] == [3, 7]
        assert recs[1]["at"] == "finish"
        assert recs[1]["preempted_s"] == pytest.approx(0.2)


def test_slo_attribution_names_dominant_phase_and_groups():
    # nine fast decode-dominated requests, one tail request that burned
    # its budget queued — the attribution must say "queue", not just
    # "p99 is high"
    events = [_tl_event(i, group="fast", dc=0.5 + 0.05 * i)
              for i in range(9)]
    events.append(_tl_event(9, group="slow", q=9.0, ttft_s=9.4))
    doc = slo_attribution(collect_timelines(events), pct=0.95)
    assert doc["requests"] == 10
    assert doc["tail"]["count"] == 1
    assert doc["tail"]["dominant_phase_counts"] == {"queue": 1}
    assert doc["tail"]["requests"][0]["request"] == 9
    assert doc["tail"]["requests"][0]["dominant_phase"] == "queue"
    # per-group rollup (the per-tenant hook): the slow group's p99
    # stands apart from the fast one's
    assert set(doc["groups"]) == {"fast", "slow"}
    assert doc["groups"]["slow"]["e2e_p99_s"] > \
        doc["groups"]["fast"]["e2e_p99_s"]
    # fractions are fractions
    for frac in doc["phase_time_frac"].values():
        assert 0.0 <= frac <= 1.0


def test_slo_attribution_groups_by_replica_when_tagged():
    """ISSUE 14: records carrying a ``replica`` tag (a multi-replica
    router run) get a per-replica rollup next to the per-group one —
    per-replica tail attribution out of the same machinery — while
    untagged (single-engine) streams stay byte-identical."""
    events = [_tl_event(i, replica=i % 2, dc=0.5 + 0.05 * i)
              for i in range(8)]
    events.append(_tl_event(8, replica=1, q=9.0, ttft_s=9.4))
    doc = slo_attribution(collect_timelines(events), pct=0.95)
    assert set(doc["replicas"]) == {"0", "1"}
    assert doc["replicas"]["0"]["requests"] == 4
    assert doc["replicas"]["1"]["requests"] == 5
    # the tail (the queue-bound request) sits on replica 1, and its
    # tail row names the replica
    assert doc["replicas"]["1"]["tail_count"] == 1
    assert doc["replicas"]["0"]["tail_count"] == 0
    assert doc["replicas"]["1"]["e2e_p99_s"] > \
        doc["replicas"]["0"]["e2e_p99_s"]
    assert doc["tail"]["requests"][0]["replica"] == 1
    # untagged records: no replicas section at all
    plain = slo_attribution(collect_timelines(
        [_tl_event(i) for i in range(4)]), pct=0.95)
    assert "replicas" not in plain
    # the text rendering names replicas
    from huggingface_sagemaker_tensorflow_distributed_tpu.obs.timeline import (
        render_slo_text,
    )

    text = render_slo_text(doc)
    assert "replica 0:" in text and "replica 1:" in text


def test_slo_attribution_rolls_up_priority_classes():
    """ISSUE 20: records carrying a ``priority`` tag (a ``policy=slo``
    run with priority classes) get a per-class rollup — attainment and
    deadline misses per class out of the same machinery. Emitters
    stamp ``priority`` absent-when-default, so untagged records in a
    tagged stream count as class 0; a wholly untagged (fifo) stream
    stays byte-identical with no priorities section at all."""
    events = [_tl_event(i, dc=0.5 + 0.05 * i, slo_met=True)
              for i in range(6)]                       # class 0, met
    events += [_tl_event(6 + i, priority=1, q=4.0 + i, ttft_s=4.2 + i,
                         slo_met=False, deadline_miss=True)
               for i in range(2)]                      # class 1, missed
    doc = slo_attribution(collect_timelines(events), pct=0.95)
    assert set(doc["priorities"]) == {"0", "1"}
    assert doc["priorities"]["0"]["requests"] == 6
    assert doc["priorities"]["1"]["requests"] == 2
    assert doc["priorities"]["0"]["slo_attainment"] == 1.0
    assert doc["priorities"]["1"]["slo_attainment"] == 0.0
    assert doc["priorities"]["1"]["deadline_misses"] == 2
    assert "deadline_misses" not in doc["priorities"]["0"]
    assert doc["priorities"]["1"]["e2e_p99_s"] > \
        doc["priorities"]["0"]["e2e_p99_s"]
    # a bool priority is not a class tag (schema types it int)
    plain = slo_attribution(collect_timelines(
        [_tl_event(i, priority=False) for i in range(4)]), pct=0.95)
    assert "priorities" not in plain
    # the text rendering names classes, attainment and misses
    from huggingface_sagemaker_tensorflow_distributed_tpu.obs.timeline import (
        render_slo_text,
    )

    text = render_slo_text(doc)
    assert "priority 0:" in text and "priority 1:" in text
    assert "attainment 0.00%" in text
    assert "2 deadline miss(es)" in text


def test_gantt_and_chrome_trace_render():
    recs = collect_timelines([_tl_event(0), _tl_event(1, pe=0.4)])
    text = gantt_text(recs, width=32)
    assert "r0" in text and "r1" in text
    assert "Q" in text and "D" in text and "X" in text
    doc = chrome_trace(recs)
    names = {e["name"] for e in doc["traceEvents"]}
    assert {"queue", "prefill", "decode", "preempted"} <= names
    assert all(e["ph"] == "X" for e in doc["traceEvents"])


# -- the tier-1 engine gate ---------------------------------------------------

@pytest.fixture(scope="module")
def tiny_gpt2():
    import jax.numpy as jnp

    from huggingface_sagemaker_tensorflow_distributed_tpu.models.auto import (
        init_params,
    )
    from huggingface_sagemaker_tensorflow_distributed_tpu.models.gpt2 import (
        Gpt2Config,
        Gpt2LMHeadModel,
    )

    cfg = Gpt2Config(vocab_size=128, hidden_size=32, num_layers=2,
                     num_heads=2, intermediate_size=64,
                     max_position_embeddings=128, hidden_dropout=0.0,
                     embd_dropout=0.0, attention_dropout=0.0,
                     eos_token_id=127, pad_token_id=0,
                     dtype=jnp.float32)
    model = Gpt2LMHeadModel(cfg)
    return cfg, model, init_params(model, cfg, seed=0)


def _run_engine(model, params, tmp, *, timeline, n_req=5,
                overlap=None):
    """A forced-preemption serve run (tight pool) with per-tenant
    groups; returns (engine, events)."""
    from huggingface_sagemaker_tensorflow_distributed_tpu.serve.engine import (
        ServeEngine,
    )

    obs.reset(out_dir=str(tmp), enabled=True)
    try:
        rng = np.random.RandomState(1)
        eng = ServeEngine(model, params, num_slots=4, block_size=4,
                          num_blocks=10, prefill_chunk=8,
                          max_model_len=32, timeline=timeline,
                          overlap=overlap)
        for i in range(n_req):
            eng.submit(rng.randint(1, 120, (9,)).astype(np.int32), 18,
                       group=f"tenant{i % 2}")
        eng.run()
        obs.flush()
    finally:
        obs.reset()
    events = [e for _, e, err in obs.iter_events(
        str(tmp / "events.jsonl")) if err is None]
    return eng, events


def test_engine_timeline_decomposition_sums_on_real_run(tiny_gpt2,
                                                        tmp_path):
    """The ISSUE 10 acceptance gate: on a real engine run under forced
    preemption, every finished request's emitted decomposition sums to
    its e2e within tolerance, the segment lists agree with the
    aggregates, the iteration ledger covers every iteration, and the
    whole stream passes the schema validator.

    ISSUE 12 extension (gate extended, not weakened): the run is a
    real OVERLAPPED forced-preemption run — the dispatch-ahead loop
    explicitly pinned on — so the decomposition must stay checkable
    with host work attributed concurrently with device time, and the
    mandatory pipeline drains (preemption acts on committed state
    only) must have latched."""
    _cfg, model, params = tiny_gpt2
    eng, events = _run_engine(model, params, tmp_path / "t",
                              timeline=True, overlap=True)
    assert eng.overlap                          # dispatch-ahead ran
    assert eng.overlap_flushes > 0              # preemption drained it
    assert eng.sched.n_preemptions > 0          # the run forced it
    recs = collect_timelines(events)
    assert sorted(r["request"] for r in recs) == \
        sorted(eng.finished.keys())
    for rec in recs:
        assert check_decomposition(rec) == [], rec["request"]
        assert rec["at"] == "finish"
        assert rec["e2e_s"] > 0 and rec["decode_s"] > 0
    # a preempted request's interval landed in the preempted phase and
    # its partial timeline was emitted at the preemption itself
    preempted = [r for r in recs if r["preemptions"] > 0]
    assert preempted and all(r["preempted_s"] > 0 for r in preempted)
    partials = [e for e in events if e.get("event") == "request_timeline"
                and e.get("at") == "preempt"]
    assert len(partials) == eng.sched.n_preemptions
    # admission-block attribution: with 5 requests over 4 tight slots
    # somebody waited at the head of the queue and says why
    blocked = [s for r in recs for s in r["segments"]
               if s.get("blocked_iters")]
    assert blocked and all(s["blocked_reason"] in
                           ("kv_capacity", "no_free_slot")
                           for s in blocked)
    # the per-iteration ledger: one event per engine iteration, token
    # accounting closed (ledger tokens sum to everything generated)
    ledgers = [e for e in events if e.get("event") == "iteration_ledger"]
    assert len(ledgers) == eng.iterations
    assert [e["iteration"] for e in ledgers] == list(range(
        eng.iterations))
    assert sum(e["tokens"] for e in ledgers) == eng.tokens_generated
    assert all(0.0 <= e["kv_used_frac"] <= 1.0 for e in ledgers)
    assert all(e["dur_s"] >= e["prefill_s"] + e["decode_s"] - 1e-5
               for e in ledgers)
    # the SLO summary aggregates close over the same accounting
    slo = eng.slo_summary()
    fracs = [slo[f"{ph}_time_frac"] for ph in
             ("queue", "prefill", "decode", "preempted", "overhead")]
    assert sum(fracs) == pytest.approx(1.0, abs=0.01)
    assert slo["preempted_time_frac"] > 0
    assert slo["queue_wait_p99_s"] >= slo["queue_wait_p50_s"] >= 0
    # the produced stream passes the schema validator end to end
    count, errors = obs.validate_events_file(
        str(tmp_path / "t" / "events.jsonl"))
    assert not errors and count > 0


def test_engine_timeline_off_restores_pre_tracing_stream(tiny_gpt2,
                                                         tmp_path):
    """HSTD_SERVE_TIMELINE=off must be byte-identical to the pre-PR
    telemetry: no new event subtypes, no new fields on existing serve
    events, no new keys in the SLO report."""
    _cfg, model, params = tiny_gpt2
    eng, events = _run_engine(model, params, tmp_path / "t",
                              timeline=False, n_req=3)
    serve_ev = [e for e in events if e["type"] == "serve"]
    kinds = {e["event"] for e in serve_ev}
    assert kinds <= {"submit", "admit", "first_token", "finish",
                     "preempt", "bucket_switch", "report"}
    new_keys = {"at", "e2e_s", "queue_s", "prefill_s", "decode_s",
                "preempted_s", "overhead_s", "segments", "group",
                "blocked_iters", "blocked_reason", "iteration",
                "dur_s", "decode_slots", "waiting", "kv_used_frac",
                "queue_wait_p50_s", "queue_wait_p99_s",
                "queue_time_frac", "prefill_time_frac",
                "decode_time_frac", "preempted_time_frac",
                "overhead_time_frac"}
    for e in serve_ev:
        leaked = new_keys & set(e)
        assert not leaked, (e["event"], leaked)
    assert not any(k in eng.slo_summary() for k in new_keys)
    # and the accounting stayed inert host-side too
    assert all(v == 0.0 for r in eng.finished.values()
               for v in r.phase_s.values())
    assert all(not r.segments for r in eng.finished.values())


def test_engine_overlap_off_restores_pre_overlap_telemetry(tiny_gpt2,
                                                           tmp_path):
    """ISSUE 12: ``HSTD_SERVE_OVERLAP=off`` must be byte-identical to
    the pre-PR (serial-loop) telemetry — allowlist-gated: no new
    event subtypes, no overlap keys on any serve event, nothing new
    in the SLO report, and the full PR-10 timeline machinery intact
    (same forced-preemption run, same decomposition gate)."""
    _cfg, model, params = tiny_gpt2
    eng, events = _run_engine(model, params, tmp_path / "t",
                              timeline=True, overlap=False)
    assert not eng.overlap and eng.overlap_flushes == 0
    assert eng.sched.n_preemptions > 0
    serve_ev = [e for e in events if e["type"] == "serve"]
    kinds = {e["event"] for e in serve_ev}
    assert kinds <= {"submit", "admit", "first_token", "finish",
                     "preempt", "bucket_switch", "report",
                     "request_timeline", "iteration_ledger"}
    for e in serve_ev:
        leaked = {"overlap", "overlap_flushes"} & set(e)
        assert not leaked, (e["event"], leaked)
    slo = eng.slo_summary()
    assert "overlap" not in slo and "overlap_flushes" not in slo
    # the serial stream still passes the full decomposition gate
    for rec in collect_timelines(events):
        assert check_decomposition(rec) == [], rec["request"]


# -- obsctl timeline|slo|tail CLI ---------------------------------------------

@pytest.fixture()
def synthetic_dirs(tmp_path):
    """Two per-host dirs of schema-valid timeline events (one tail
    request dominated by queue, one preempted request)."""
    a = [_tl_event(0, group="t0"), _tl_event(2, pe=0.4, group="t0"),
         _ledger_event(0), _ledger_event(1, t=1001.0)]
    b = [_tl_event(1, group="t1"), _tl_event(3, q=6.0, group="t1")]
    _write_events(str(tmp_path / "h0" / "events.jsonl"), a)
    _write_events(str(tmp_path / "h1" / "events.jsonl"), b)
    return [str(tmp_path / "h0"), str(tmp_path / "h1")]


def _run_obsctl(*argv):
    return subprocess.run([sys.executable, _OBSCTL, *argv],
                          stdout=subprocess.PIPE,
                          stderr=subprocess.PIPE, text=True, cwd=_REPO)


def test_cli_timeline_gantt_trace_and_determinism(synthetic_dirs,
                                                  tmp_path):
    from huggingface_sagemaker_tensorflow_distributed_tpu.obs.schema import (
        validate_trace_file,
    )

    trace = str(tmp_path / "chrome.json")
    proc = _run_obsctl("timeline", *synthetic_dirs, "--trace", trace)
    assert proc.returncode == 0, proc.stderr
    assert "r0" in proc.stdout and "r3" in proc.stdout
    n, errors = validate_trace_file(trace)
    assert n > 0 and not errors
    # byte-identical across input orderings (trace file too)
    rev = _run_obsctl("timeline", *reversed(synthetic_dirs),
                      "--trace", str(tmp_path / "chrome2.json"))
    assert rev.returncode == 0 and rev.stdout == proc.stdout
    assert (tmp_path / "chrome.json").read_bytes() == \
        (tmp_path / "chrome2.json").read_bytes()
    js = _run_obsctl("timeline", "--json", *synthetic_dirs)
    recs = json.loads(js.stdout)
    assert [r["request"] for r in recs] == [0, 1, 2, 3]


def test_cli_slo_attribution_and_determinism(synthetic_dirs):
    proc = _run_obsctl("slo", *synthetic_dirs, "--percentile", "90")
    assert proc.returncode == 0, proc.stderr
    doc = json.loads(proc.stdout)
    assert doc["tail"]["dominant_phase_counts"] == {"queue": 1}
    assert set(doc["groups"]) == {"t0", "t1"}
    rev = _run_obsctl("slo", *reversed(synthetic_dirs),
                      "--percentile", "90")
    assert rev.stdout == proc.stdout
    text = _run_obsctl("slo", "--text", *synthetic_dirs)
    assert text.returncode == 0 and "dominated by queue" in text.stdout


def test_cli_timeline_and_slo_reject_malformed_input(tmp_path):
    bad = tmp_path / "bad"
    bad.mkdir()
    (bad / "events.jsonl").write_text(
        '{"torn json\n'
        + json.dumps(_tl_event(0)) + "\n")
    for cmd in ("timeline", "slo"):
        proc = _run_obsctl(cmd, str(bad))
        assert proc.returncode == 1
        assert "unparseable" in proc.stderr
    # mistyped field -> schema validation failure, not silent garbage
    drift = tmp_path / "drift"
    drift.mkdir()
    ev = _tl_event(0)
    ev["queue_s"] = "fast"
    _write_events(str(drift / "events.jsonl"), [ev])
    proc = _run_obsctl("timeline", str(drift))
    assert proc.returncode == 1 and "queue_s" in proc.stderr
    # internally inconsistent decomposition -> rejected too
    sick = tmp_path / "sick"
    sick.mkdir()
    ev = _tl_event(0)
    ev["decode_s"] = 40.0
    _write_events(str(sick / "events.jsonl"), [ev])
    proc = _run_obsctl("timeline", str(sick))
    assert proc.returncode == 1 and "inconsistent" in proc.stderr
    # empty input
    empty = tmp_path / "empty"
    empty.mkdir()
    assert _run_obsctl("timeline", str(empty)).returncode == 1
    assert _run_obsctl("tail", str(empty / "nope.jsonl")).returncode == 1
    # bad knob values: clean diagnostic + exit 1, not a traceback
    good = tmp_path / "good"
    good.mkdir()
    _write_events(str(good / "events.jsonl"), [_tl_event(0)])
    proc = _run_obsctl("timeline", str(good), "--width", "0")
    assert proc.returncode == 1 and "--width" in proc.stderr
    proc = _run_obsctl("slo", str(good), "--percentile", "0")
    assert proc.returncode == 1 and "--percentile" in proc.stderr
    seeded = str(good / "events.jsonl")
    proc = _run_obsctl("tail", seeded, "--window", "0", "--updates", "1")
    assert proc.returncode == 1 and "--window" in proc.stderr


def test_cli_tail_follows_live_appends(tmp_path):
    """The live-follow contract end to end: the subprocess prints one
    rolling-gauge line per poll that saw new events and picks up lines
    appended AFTER it started."""
    path = str(tmp_path / "events.jsonl")
    _write_events(path, [_ledger_event(0, waiting=4)])
    proc = subprocess.Popen(
        [sys.executable, _OBSCTL, "tail", path, "--updates", "2",
         "--interval", "0.1"],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        cwd=_REPO)
    try:
        # BLOCK on the first update line (no startup race): line 1 was
        # pre-seeded, so its gauge line proves the first poll landed
        first = proc.stdout.readline()
        with open(path, "a", encoding="utf-8") as f:
            f.write(json.dumps(_ledger_event(1, t=1001.0, waiting=7))
                    + "\n")
        out, err = proc.communicate(timeout=30)
    finally:
        proc.kill()
    assert proc.returncode == 0, err
    lines = [ln for ln in (first + out).splitlines() if ln.strip()]
    assert len(lines) == 2
    assert "waiting=4" in lines[0]
    assert "waiting=7" in lines[1] and "iter=1" in lines[1]


def test_cli_tail_exits_nonzero_on_malformed_stream(tmp_path):
    path = str(tmp_path / "events.jsonl")
    _write_events(path, [_ledger_event(0)])
    with open(path, "a", encoding="utf-8") as f:
        f.write("not json at all\n")
    proc = _run_obsctl("tail", path, "--updates", "5", "--interval",
                       "0.05")
    assert proc.returncode == 1
    assert "unparseable" in proc.stderr


# -- the no-jax import contract, extended (ISSUE 10 satellite) ----------------

def test_obs_timeline_runs_without_jax():
    """obs/timeline.py and every obsctl subcommand stay on the
    stdlib-only side of the obs contract — asserted statically via
    graftlint R1's import-time reachability (ISSUE 15): complete over
    all import edges, not just the subcommand paths a poison run
    happened to execute. The slow-tier subprocess smokes
    (test_obsctl / test_telemetry_schema) backstop the static view at
    runtime."""
    from huggingface_sagemaker_tensorflow_distributed_tpu.analysis.lint import (
        PACKAGE,
        load_project,
    )
    from huggingface_sagemaker_tensorflow_distributed_tpu.analysis.rules import (
        check_r1,
        r1_reachability,
        r1_zone_roots,
    )

    project = load_project(_REPO)
    assert check_r1(project) == []
    # timeline is a zone ROOT (all of obs/ is), so even its
    # lazily-imported consumers can't smuggle jax in at import time
    assert f"{PACKAGE}/obs/timeline.py" in r1_zone_roots(project)
    # the fleet-trace stitcher (ISSUE 19) rides the same contract —
    # `obsctl trace|fleet` run on the same jax-less boxes
    assert f"{PACKAGE}/obs/trace.py" in r1_zone_roots(project)
    assert "scripts/obsctl.py" in r1_reachability(project)
