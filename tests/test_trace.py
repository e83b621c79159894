"""Fleet-level distributed request tracing (ISSUE 19): cross-engine
stitching must reassemble a migrated request's whole history into ONE
causal trace whose hop-aware decomposition (router_queue + prefill +
transport + decode_admission + decode + preempted + overhead) telescopes
exactly to e2e, degrade torn/partial streams to FLAGGED-incomplete
traces (never wrong ones), roll stitched traces into byte-deterministic
fleet attribution (``obsctl trace|fleet``), and hold on a REAL forced
mid-decode migration — all on the stdlib-only side of the obs contract.
"""

import json
import os
import random
import subprocess
import sys

import numpy as np
import pytest

from huggingface_sagemaker_tensorflow_distributed_tpu.obs.trace import (
    TRACE_PHASES,
    check_trace,
    collect_traces,
    fleet_chrome_trace,
    fleet_summary,
    fleet_text,
    trace_text,
)

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_OBSCTL = os.path.join(_REPO, "scripts", "obsctl.py")


# -- synthetic traced streams (pure host, no jax) -----------------------------

def _sub(tid, rid, t=1000.0, replica=0):
    return {"v": 1, "t": t, "host": 0, "pid": 1, "type": "serve",
            "event": "submit", "request": rid, "max_new_tokens": 10,
            "trace_id": tid, "hop": 0, "replica": replica}


def _mig(tid, rid, t=1000.35, hop=1, frm=0, to=1, extract=0.02,
         restore=0.01, hop_s=0.06, **extra):
    """One hot migrate event pricing the hop: transport_hop_s covers
    the hold segment (0.05) + restore (0.01) exactly by default."""
    ev = {"v": 1, "t": t, "host": 0, "pid": 1, "type": "serve",
          "event": "migrate", "request": rid, "from_replica": frm,
          "to_replica": to, "migration_bytes": 4096,
          "restore_s": restore, "extract_s": extract,
          "transport_hop_s": hop_s, "trace_id": tid, "hop": hop}
    ev.update(extra)
    return ev


def _tl(tid, rid, t=1000.8, at="finish", hop=1, group="", **over):
    """The finish timeline of a one-hop migrated request whose
    aggregates and segments agree by construction: queue 0.1 @r0,
    prefill 0.2 @r0, migration hold 0.05 @r1 (via=migrate, hop 1),
    decode 0.4 @r1, overhead 0.05 (of which 0.01 is the restore)."""
    ev = {"v": 1, "t": t, "host": 0, "pid": 1, "type": "serve",
          "event": "request_timeline", "request": rid, "at": at,
          "e2e_s": 0.8, "queue_s": 0.1, "prefill_s": 0.2,
          "decode_s": 0.4, "preempted_s": 0.05, "overhead_s": 0.05,
          "tokens": 10, "prompt_len": 5, "preemptions": 1,
          "ttft_s": 0.3, "trace_id": tid, "hop": hop, "replica": 1,
          "segments": [
              {"ph": "queue", "t0": 0.0, "dur": 0.1, "replica": 0},
              {"ph": "prefill", "t0": 0.1, "dur": 0.2, "from": 0,
               "chunks": 1, "replica": 0},
              {"ph": "preempted", "t0": 0.3, "dur": 0.05,
               "via": "migrate", "hop": 1, "replica": 1},
              {"ph": "decode", "t0": 0.36, "dur": 0.4, "bucket": 64,
               "iters": 10, "tokens": 10, "replica": 1},
          ]}
    if group:
        ev["group"] = group
    ev.update(over)
    return ev


def _one_hop(tid="t000000", rid=0, t=1000.0, group=""):
    return [_sub(tid, rid, t=t),
            _mig(tid, rid, t=t + 0.35),
            _tl(tid, rid, t=t + 0.8, group=group)]


def _write_events(path, events):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w", encoding="utf-8") as f:
        for e in events:
            f.write(json.dumps(e) + "\n")


# -- the stitch ----------------------------------------------------------------

def test_stitch_one_hop_complete_and_telescoped_phases():
    """The core contract: a migrated request's events — in ANY input
    order — stitch into one complete trace whose cross-hop phases
    telescope exactly to e2e and pass every consistency check."""
    events = _one_hop()
    shuffled = list(events)
    random.Random(7).shuffle(shuffled)
    for stream in (events, list(reversed(events)), shuffled):
        traces = collect_traces(stream)
        assert len(traces) == 1
        tr = traces[0]
        assert tr["complete"] and tr["incomplete"] == []
        assert tr["trace_id"] == "t000000" and tr["request"] == 0
        assert tr["hops"] == 1 and tr["replicas"] == [0, 1]
        assert tr["e2e_s"] == 0.8 and tr["ttft_s"] == 0.3
        # the telescoped decomposition: tagged hold seconds move into
        # transport/decode_admission, restore out of overhead
        assert tr["phases"] == {
            "router_queue": 0.1, "prefill": 0.2, "transport": 0.03,
            "decode_admission": 0.03, "decode": 0.4,
            "preempted": 0.0, "overhead": 0.04}
        assert sum(tr["phases"][ph] for ph in TRACE_PHASES) \
            == pytest.approx(0.8)
        assert check_trace(tr) == []


def test_stitch_keeps_router_scoped_ids_apart():
    """Trace ids are router-scoped sequences: the same id from two
    processes (two runs appended into one stream) must NOT merge."""
    a = _one_hop("t000000", rid=0)
    b = [dict(e, pid=2) for e in _one_hop("t000000", rid=5)]
    traces = collect_traces(a + b)
    assert len(traces) == 2
    assert sorted(t["request"] for t in traces) == [0, 5]
    assert all(t["complete"] for t in traces)


def test_stitch_degrades_torn_and_partial_streams_to_flagged():
    """Incompleteness is FLAGGED, never silently wrong: a torn tail
    (no timeline), a preempt-partial final timeline, a finish at a
    stale hop, and a hop with no migrate/requeue evidence each name
    their reason; check_trace treats flagged traces as non-errors."""
    sub, mig, tl = _one_hop()
    # torn tail: lifecycle events but the timeline never landed
    (tr,) = collect_traces([sub, mig])
    assert not tr["complete"]
    assert any("torn tail" in r for r in tr["incomplete"])
    assert check_trace(tr) == []
    # final timeline is a preempt-requeue partial, not a finish
    (tr,) = collect_traces([sub, mig, dict(tl, at="preempt")])
    assert not tr["complete"]
    assert any("not finish" in r for r in tr["incomplete"])
    # stale finish: hop-2 evidence exists but the finish is hop-1
    mig2 = _mig("t000000", 0, t=1000.5, hop=2, frm=1, to=0)
    (tr,) = collect_traces([sub, mig, mig2, tl])
    assert not tr["complete"]
    assert any("stale finish" in r for r in tr["incomplete"])
    # missing hop evidence: the finish claims hop 1 but no migrate or
    # requeue event ever recorded the move
    (tr,) = collect_traces([sub, tl])
    assert not tr["complete"]
    assert any("missing hop 1 evidence" in r for r in tr["incomplete"])
    # a trace spanning two request ids is flagged, not merged
    (tr,) = collect_traces([sub, mig, dict(tl, request=9)])
    assert any("request ids" in r for r in tr["incomplete"])
    # rendering an incomplete trace narrates the flags
    text = trace_text(tr)
    assert "INCOMPLETE" in text


def test_check_trace_names_gap_overlap_and_sum_bugs():
    """The consistency checks catch REAL accounting bugs: an inflated
    hop clock is an inter-hop gap, a deflated one an overlap, a
    priced hop without its hold segment is named, and a tampered
    aggregate fails both the five-way and telescoped sums."""
    sub, mig, tl = _one_hop()
    # inflated transport_hop_s: time lost between engines
    (tr,) = collect_traces([sub, dict(mig, transport_hop_s=0.2), tl])
    assert any("inter-hop gap" in e for e in check_trace(tr))
    # deflated: the hold segment claims more than the hop clock saw
    (tr,) = collect_traces([sub, dict(mig, transport_hop_s=0.01), tl])
    assert any("overlap" in e for e in check_trace(tr))
    # a priced hop whose migration hold never closed
    bad_tl = _tl("t000000", 0)
    bad_tl["segments"] = [s for s in bad_tl["segments"]
                          if s.get("via") != "migrate"]
    bad_tl["preempted_s"] = 0.0
    bad_tl["decode_s"] = 0.45    # keep the five-way sum consistent
    bad_tl["segments"][-1] = dict(bad_tl["segments"][-1], dur=0.45)
    (tr,) = collect_traces([sub, mig, bad_tl])
    assert any("no migration-hold segment" in e for e in check_trace(tr))
    # a tampered aggregate: the underlying five-way contract fires and
    # the telescoped sum breaks with it
    (tr,) = collect_traces([sub, mig, _tl("t000000", 0, decode_s=0.6)])
    errs = check_trace(tr)
    assert any("cross-hop phase sum" in e for e in errs)
    assert errs and check_trace(collect_traces([sub, mig, _tl(
        "t000000", 0)])[0]) == []


# -- fleet rollups -------------------------------------------------------------

def test_fleet_summary_counts_roles_replicas_and_tenants():
    events = (_one_hop("t000000", 0, t=1000.0, group="tenantA")
              + _one_hop("t000001", 1, t=1002.0, group="tenantB"))
    traces = collect_traces(events)
    s = fleet_summary(traces)
    assert (s["traces"], s["complete_traces"],
            s["trace_stitch_failures"]) == (2, 2, 0)
    assert s["phase_total_s"]["transport"] == pytest.approx(0.06)
    assert s["phase_frac"]["decode"] == pytest.approx(0.5)
    # fleet percentiles use the router's nearest-rank convention
    assert s["ttft_p50_s"] == 0.3 and s["ttft_p99_s"] == 0.3
    assert s["e2e_p50_s"] == 0.8
    assert s["transport_hops"] == 2 and s["migration_bytes"] == 8192
    assert s["transport_hop_s_p99"] == 0.06
    # roles are inferred from WHERE segments ran, no config needed
    assert s["per_role"]["prefill"]["replicas"] == [0]
    assert s["per_role"]["decode"]["replicas"] == [1]
    assert s["per_role"]["prefill"]["ttft_p50_s"] == 0.3
    assert "tpot_p50_s" in s["per_role"]["decode"]
    assert s["per_replica"]["0"]["prefill_s"] == pytest.approx(0.4)
    assert s["per_replica"]["1"]["decode_s"] == pytest.approx(0.8)
    assert s["per_replica"]["0"]["role"] == "prefill"
    assert set(s["per_group"]) == {"tenantA", "tenantB"}
    assert s["per_group"]["tenantA"]["traces"] == 1
    # an incomplete trace shifts the stitch counters, not the rollup
    s2 = fleet_summary(collect_traces(
        events + [_sub("t000002", 2, t=1004.0)]))
    assert s2["trace_stitch_failures"] == 1
    assert s2["incomplete"][0]["trace_id"] == "t000002"
    assert "stitch failure" in fleet_text(collect_traces(events))


def test_fleet_chrome_trace_multi_track_with_flow_arrows(tmp_path):
    """The merged export: one pid per REPLICA, and each hop drawn as
    an s->f flow pair crossing tracks at the right instants."""
    from huggingface_sagemaker_tensorflow_distributed_tpu.obs.schema import (
        validate_trace_file,
    )

    traces = collect_traces(_one_hop())
    doc = fleet_chrome_trace(traces)
    xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert [e["pid"] for e in xs] == [0, 0, 1, 1]   # segs on their replica
    assert all(e["tid"] == 0 for e in xs)
    flows = [e for e in doc["traceEvents"] if e["ph"] in ("s", "f")]
    assert [e["ph"] for e in flows] == ["s", "f"]
    assert all(e["id"] == "t000000/1" and e["cat"] == "transport"
               for e in flows)
    assert flows[0]["pid"] == 0 and flows[1]["pid"] == 1
    assert flows[1]["bp"] == "e"
    # the arrow spans source prefill end -> hold segment end
    assert flows[1]["ts"] - flows[0]["ts"] == pytest.approx(0.05e6)
    path = str(tmp_path / "fleet.json")
    with open(path, "w", encoding="utf-8") as f:
        json.dump(doc, f)
    n, errors = validate_trace_file(path)
    assert n == len(doc["traceEvents"]) and not errors


def test_chrome_timeline_per_replica_tracks():
    """Regression (ISSUE 19 satellite): ``obsctl timeline --trace``
    folded a whole router fleet — one OS process — onto one viewer
    track. Replica-tagged records now get their own stable pid;
    untagged single-engine exports keep pid 0."""
    from huggingface_sagemaker_tensorflow_distributed_tpu.obs.timeline import (
        chrome_trace,
        collect_timelines,
    )

    recs = collect_timelines([
        _tl("", 0, replica=0, trace_id=None, hop=None),
        _tl("", 1, t=1001.0, replica=1, trace_id=None, hop=None),
    ])
    doc = chrome_trace(recs)
    pids = {e["args"]["request"]: e["pid"] for e in doc["traceEvents"]}
    assert pids[0] != pids[1]
    # untagged records keep the single-track projection
    untagged = collect_timelines([
        _tl("", 0, replica=None, trace_id=None, hop=None),
        _tl("", 1, t=1001.0, replica=None, trace_id=None, hop=None),
    ])
    assert {e["pid"] for e in chrome_trace(untagged)["traceEvents"]} \
        == {0}


# -- schema: mistyped trace context is rejected, not silently consumed --------

def test_schema_rejects_mistyped_trace_context_fields():
    from huggingface_sagemaker_tensorflow_distributed_tpu.obs.schema import (
        validate_event,
    )

    good = _mig("t000000", 0)
    assert validate_event(good) == []
    for field, bad in (("trace_id", 7), ("hop", "one"),
                       ("hop", True), ("replica", "0"),
                       ("transport_hop_s", "fast"),
                       ("extract_s", [0.02])):
        errs = validate_event(dict(good, **{field: bad}))
        assert errs and any(field in e for e in errs), (field, bad)
    stitch = {"v": 1, "t": 1000.0, "host": 0, "pid": 1,
              "type": "serve", "event": "trace_stitch", "traces": 8,
              "complete_traces": 8, "trace_stitch_failures": 0,
              "transport_hop_s_p99": 0.004}
    assert validate_event(stitch) == []
    assert validate_event(dict(stitch, trace_stitch_failures="0"))
    assert validate_event(dict(stitch, complete_traces=7.5))


# -- the real thing: forced mid-decode migration ------------------------------

def test_engine_mid_decode_migration_stitches_complete(gpt2_setup,
                                                       tmp_path):
    """End to end on real engines: a request migrated MID-DECODE
    leaves a stream that stitches into one complete hop-1 trace whose
    cross-hop decomposition passes every check, with the transport
    phase priced (> 0) and the hot migrate event carrying the hop
    clock. Tokens stay exact under tracing (the PR 18 contract)."""
    from huggingface_sagemaker_tensorflow_distributed_tpu import obs
    from huggingface_sagemaker_tensorflow_distributed_tpu.serve.engine import (
        ServeEngine,
    )
    from huggingface_sagemaker_tensorflow_distributed_tpu.serve.transport import (
        migrate_request,
    )

    _cfg, model, params = gpt2_setup
    kw = dict(num_slots=2, block_size=4, num_blocks=40,
              prefill_chunk=8, max_model_len=64,
              gather_buckets=[16, 32], timeline="on")
    rng = np.random.RandomState(3)
    prompt = rng.randint(1, 120, (9,)).astype(np.int32)

    base_eng = ServeEngine(model, params, **kw)
    base_req = base_eng.submit(prompt, 10)
    base_eng.run()
    base = list(base_eng.output_ids(base_req))

    out = tmp_path / "mid_decode"
    obs.reset(out_dir=str(out), enabled=True)
    try:
        src = ServeEngine(model, params, **kw)
        dst = ServeEngine(model, params, **kw)
        src.replica, dst.replica = 0, 1
        req = src.submit(prompt, 10, trace_id="t000000")
        while src.has_work() and len(req.output) < 4:
            src.step()
        assert len(req.output) >= 1                  # mid-decode
        assert migrate_request(src, dst, req.rid) is not None
        assert req.hop == 1
        dst.run()
        obs.flush()
    finally:
        obs.reset()
    assert list(dst.output_ids(req)) == base

    from huggingface_sagemaker_tensorflow_distributed_tpu.obs.timeline import (
        load_events,
    )
    events, errors = load_events([str(out)])
    assert not errors
    traces = collect_traces(events)
    assert len(traces) == 1
    tr = traces[0]
    assert tr["complete"], tr["incomplete"]
    assert tr["hops"] == 1 and tr["replicas"] == [0, 1]
    assert check_trace(tr) == []
    assert tr["phases"]["transport"] > 0
    (mig,) = tr["migrates"]
    assert mig["transport_hop_s"] >= mig["extract_s"] >= 0
    assert mig["from_replica"] == 0 and mig["to_replica"] == 1
    # the stitched ttft matches the engine's own stamp to the rounding
    assert tr["ttft_s"] == pytest.approx(req.ttft_s, abs=1e-6)


def test_disaggregated_fleet_stitches_every_request_and_reconciles(
        gpt2_setup, tmp_path):
    """A traced prefill:1,decode:1 fleet under an open-loop schedule:
    every request stitches into ONE complete trace with its migration
    in it, every trace passes the cross-hop decomposition check, and the
    stitcher's prefill-side TTFT percentiles equal the router's own
    per-role report to the digit (same nearest-rank percentile, same
    rounding: any daylight is an attribution bug)."""
    from huggingface_sagemaker_tensorflow_distributed_tpu import obs
    from huggingface_sagemaker_tensorflow_distributed_tpu.obs.timeline import (
        load_events,
    )
    from huggingface_sagemaker_tensorflow_distributed_tpu.serve.loadgen import (
        OpenLoopDriver,
        SloSpec,
        make_schedule,
    )
    from huggingface_sagemaker_tensorflow_distributed_tpu.serve.router import (
        Router,
    )

    cfg, model, params = gpt2_setup
    n_req, rate = 8, 300.0
    schedule = make_schedule(
        n_req, 120, process="poisson", rate=rate, seed=11, prompt_lo=4,
        prompt_hi=16, new_lo=3, new_hi=6, eos_token_id=cfg.eos_token_id)
    out = tmp_path / "fleet"
    obs.reset(out_dir=str(out), enabled=True)
    try:
        router = Router(model, params, roles={"prefill": 1, "decode": 1},
                        num_slots=2, block_size=4, num_blocks=40,
                        prefill_chunk=8, max_model_len=64,
                        gather_buckets=[16, 32], prefix_cache=False,
                        timeline="on", trace="on")
        finished = OpenLoopDriver(
            router, schedule, clock="virtual", tick_s=0.001,
            slo=SloSpec(ttft_s=0.02), process="poisson", rate=rate).run()
        obs.flush()
    finally:
        obs.reset()
    assert len(finished) == n_req
    events, errors = load_events([str(out)])
    assert not errors
    traces = collect_traces(events)
    fleet = fleet_summary(traces)
    assert len(traces) == fleet["complete_traces"] == n_req
    assert fleet["trace_stitch_failures"] == 0
    assert all(len(t["migrates"]) >= 1 for t in traces)
    assert [p for t in traces for p in check_trace(t)] == []
    by_router = router.slo_summary()["per_role"]["prefill"]
    for key in ("ttft_p50_s", "ttft_p95_s", "ttft_p99_s"):
        assert fleet["per_role"]["prefill"][key] == by_router[key] > 0


def test_engine_untraced_stream_carries_no_trace_fields(gpt2_setup,
                                                        tmp_path):
    """The absent-when-default contract: without a trace_id, no event
    gains trace_id/hop — the stream stays byte-compatible with the
    pre-tracing schema and the stitcher finds nothing to stitch."""
    from huggingface_sagemaker_tensorflow_distributed_tpu import obs
    from huggingface_sagemaker_tensorflow_distributed_tpu.serve.engine import (
        ServeEngine,
    )

    _cfg, model, params = gpt2_setup
    out = tmp_path / "untraced"
    obs.reset(out_dir=str(out), enabled=True)
    try:
        eng = ServeEngine(model, params, num_slots=2, block_size=4,
                          num_blocks=40, prefill_chunk=8,
                          max_model_len=64, gather_buckets=[16, 32],
                          timeline="on")
        eng.submit(np.arange(1, 9, dtype=np.int32), 4)
        eng.run()
        obs.flush()
    finally:
        obs.reset()
    from huggingface_sagemaker_tensorflow_distributed_tpu.obs.timeline import (
        load_events,
    )
    events, errors = load_events([str(out)])
    assert not errors and events
    assert all("trace_id" not in e and "hop" not in e for e in events)
    assert collect_traces(events) == []


# -- the CLI: byte-deterministic trace/fleet ----------------------------------

def _run_obsctl(*argv):
    return subprocess.run([sys.executable, _OBSCTL, *argv],
                          stdout=subprocess.PIPE,
                          stderr=subprocess.PIPE, text=True, cwd=_REPO)


@pytest.fixture()
def stitched_dirs(tmp_path):
    """One traced run split across two event files the way a fleet
    writes them — the stitch must not care which file holds what."""
    a = tmp_path / "hostA"
    b = tmp_path / "hostB"
    sub, mig, tl = _one_hop("t000000", 0, group="tenantA")
    sub2, mig2, tl2 = _one_hop("t000001", 1, t=1002.0)
    _write_events(str(a / "events.jsonl"), [sub, mig, sub2])
    _write_events(str(b / "events.jsonl"), [tl, mig2, tl2])
    return [str(a), str(b)]


def test_cli_trace_narrative_and_determinism(stitched_dirs):
    proc = _run_obsctl("trace", "t000000", *stitched_dirs)
    assert proc.returncode == 0, proc.stderr
    assert "trace t000000" in proc.stdout
    assert "cross-hop decomposition" in proc.stdout
    assert "transport" in proc.stdout and "[migration hold]" in proc.stdout
    rev = _run_obsctl("trace", "t000000", *reversed(stitched_dirs))
    assert rev.returncode == 0 and rev.stdout == proc.stdout
    # selection by request id renders the same trace
    by_rid = _run_obsctl("trace", "0", *stitched_dirs)
    assert by_rid.returncode == 0 and by_rid.stdout == proc.stdout
    # unknown id: loud rc 1 with the known ids named
    missing = _run_obsctl("trace", "t999999", *stitched_dirs)
    assert missing.returncode == 1 and "t000000" in missing.stderr


def test_cli_trace_flags_incomplete_with_rc1(tmp_path):
    d = tmp_path / "torn"
    sub, mig, _tl_ = _one_hop()
    _write_events(str(d / "events.jsonl"), [sub, mig])   # torn tail
    proc = _run_obsctl("trace", "t000000", str(d))
    assert proc.returncode == 1
    assert "INCOMPLETE" in proc.stdout and "torn tail" in proc.stdout


def test_cli_fleet_table_json_trace_and_determinism(stitched_dirs,
                                                    tmp_path):
    from huggingface_sagemaker_tensorflow_distributed_tpu.obs.schema import (
        validate_trace_file,
    )

    proc = _run_obsctl("fleet", *stitched_dirs)
    assert proc.returncode == 0, proc.stderr
    assert "2 trace(s), 2 complete" in proc.stdout
    assert "role prefill" in proc.stdout and "tenantA" in proc.stdout
    rev = _run_obsctl("fleet", *reversed(stitched_dirs))
    assert rev.returncode == 0 and rev.stdout == proc.stdout
    js = _run_obsctl("fleet", "--json", *stitched_dirs)
    doc = json.loads(js.stdout)
    assert doc["complete_traces"] == 2
    assert doc["per_role"]["prefill"]["ttft_p50_s"] == 0.3
    # the merged chrome export is byte-identical under input order too
    t1, t2 = str(tmp_path / "f1.json"), str(tmp_path / "f2.json")
    assert _run_obsctl("fleet", *stitched_dirs,
                       "--trace", t1).returncode == 0
    assert _run_obsctl("fleet", *reversed(stitched_dirs),
                       "--trace", t2).returncode == 0
    with open(t1, "rb") as f1, open(t2, "rb") as f2:
        assert f1.read() == f2.read()
    n, errors = validate_trace_file(t1)
    assert n > 0 and not errors


def test_cli_fleet_rejects_malformed_and_inconsistent_input(tmp_path):
    bad = tmp_path / "bad"
    bad.mkdir()
    (bad / "events.jsonl").write_text(
        '{"torn json\n' + json.dumps(_sub("t000000", 0)) + "\n")
    proc = _run_obsctl("fleet", str(bad))
    assert proc.returncode == 1 and "unparseable" in proc.stderr
    # a claimed-complete trace with broken accounting exits 1
    sick = tmp_path / "sick"
    sub, mig, _tl_ = _one_hop()
    _write_events(str(sick / "events.jsonl"),
                  [sub, dict(mig, transport_hop_s=0.5),
                   _tl("t000000", 0)])
    proc = _run_obsctl("fleet", str(sick))
    assert proc.returncode == 1 and "inter-hop gap" in proc.stderr
    # no traced events at all: named, rc 1
    empty = tmp_path / "empty"
    _write_events(str(empty / "events.jsonl"),
                  [dict(_sub("", 0), trace_id=None, hop=None,
                        replica=None)])
    proc = _run_obsctl("fleet", str(empty))
    assert proc.returncode == 1 and "no traced serve events" in proc.stderr
