"""Open-loop load generation + SLO contract (ISSUE 16,
``serve/loadgen.py`` + the engine's deadline fields): the arrival
generators are pure functions of their seeds, the virtual-clock driver
is byte-replayable (including Router ``replicas=1`` vs the bare
engine), overload is
queue-attributed, and every new telemetry field stays ABSENT on a
closed-loop run (the byte-identity contract for pre-16 streams)."""

import json

import pytest

from huggingface_sagemaker_tensorflow_distributed_tpu import obs
from huggingface_sagemaker_tensorflow_distributed_tpu.serve.loadgen import (
    ENV_ARRIVAL,
    ENV_ARRIVAL_SEED,
    ENV_SLO_TPOT,
    ENV_SLO_TTFT,
    OpenLoopDriver,
    SloSpec,
    bursty_arrivals,
    heavy_tailed_lengths,
    make_schedule,
    parse_arrival,
    parse_arrival_seed,
    parse_slo,
    poisson_arrivals,
)


# -- generators (pure host) --------------------------------------------------

def test_poisson_arrivals_deterministic_monotone():
    a = poisson_arrivals(10.0, 50, seed=3)
    assert a == poisson_arrivals(10.0, 50, seed=3)
    assert a != poisson_arrivals(10.0, 50, seed=4)
    assert len(a) == 50
    assert all(b > c for b, c in zip(a[1:], a))    # strictly increasing
    with pytest.raises(ValueError):
        poisson_arrivals(0.0, 5)


def test_bursty_arrivals_deterministic_and_bursty():
    a = bursty_arrivals(50.0, 1.0, 0.2, 80, seed=7)
    assert a == bursty_arrivals(50.0, 1.0, 0.2, 80, seed=7)
    assert all(b > c for b, c in zip(a[1:], a))
    # two very different state rates must leave a visible gap spread —
    # the burst/lull signature a rate-matched plain Poisson lacks
    gaps = sorted(b - c for b, c in zip(a[1:], a))
    assert gaps[-1] / max(gaps[0], 1e-12) > 10
    with pytest.raises(ValueError):
        bursty_arrivals(5.0, 0.0, 0.1, 5)
    with pytest.raises(ValueError):
        bursty_arrivals(5.0, 1.0, 1.5, 5)


def test_heavy_tailed_lengths_bounded_deterministic():
    ls = heavy_tailed_lengths(200, 4, 64, seed=1, alpha=1.2)
    assert ls == heavy_tailed_lengths(200, 4, 64, seed=1, alpha=1.2)
    assert all(4 <= v <= 64 for v in ls)
    # bounded Pareto: mass near lo, tail reaching high
    assert sorted(ls)[len(ls) // 2] < 16 < max(ls)
    with pytest.raises(ValueError):
        heavy_tailed_lengths(5, 0, 8)
    with pytest.raises(ValueError):
        heavy_tailed_lengths(5, 4, 8, alpha=0.0)


def test_make_schedule_deterministic_sorted_and_grouped():
    kw = dict(process="bursty", rate=40.0, rate_lo=4.0, p_switch=0.3,
              seed=9, prompt_lo=2, prompt_hi=6, new_lo=2, new_hi=5,
              eos_token_id=63, groups=("a", "b", "c"))
    sched = make_schedule(12, 64, **kw)
    assert sched == make_schedule(12, 64, **kw)
    assert [t for t, _ in sched] == sorted(t for t, _ in sched)
    for i, (_, spec) in enumerate(sched):
        assert 2 <= len(spec["prompt"]) <= 6
        assert 2 <= spec["max_new_tokens"] <= 5
        assert 63 not in spec["prompt"]            # eos never in prompts
        assert spec["group"] == ("a", "b", "c")[i % 3]
    with pytest.raises(ValueError):
        make_schedule(4, 64, process="uniform")


# -- knob parsing ------------------------------------------------------------

def test_slospec_validation():
    assert SloSpec(ttft_s=0.5).tpot_s is None
    with pytest.raises(ValueError):
        SloSpec()                                  # no target at all
    with pytest.raises(ValueError):
        SloSpec(ttft_s=0.0)
    with pytest.raises(ValueError):
        SloSpec(tpot_s=-1.0)


def test_parse_arrival_specs_and_env(monkeypatch):
    assert parse_arrival("closed") is None
    assert parse_arrival("poisson:2.5") == ("poisson", {"rate": 2.5})
    assert parse_arrival("bursty:4,0.5,0.25") == (
        "bursty", {"rate_hi": 4.0, "rate_lo": 0.5, "p_switch": 0.25})
    for bad in ("poisson", "poisson:0", "bursty:1,2", "wat:1"):
        with pytest.raises(ValueError):
            parse_arrival(bad)
    monkeypatch.delenv(ENV_ARRIVAL, raising=False)
    assert parse_arrival() is None                 # default: closed
    monkeypatch.setenv(ENV_ARRIVAL, "poisson:8")
    assert parse_arrival() == ("poisson", {"rate": 8.0})


def test_parse_arrival_seed_env(monkeypatch):
    monkeypatch.delenv(ENV_ARRIVAL_SEED, raising=False)
    assert parse_arrival_seed() == 0
    monkeypatch.setenv(ENV_ARRIVAL_SEED, "42")
    assert parse_arrival_seed() == 42
    with pytest.raises(ValueError):
        parse_arrival_seed("x")


def test_parse_slo_specs_and_env(monkeypatch):
    assert parse_slo("none") is None
    assert parse_slo("ttft:0.5") == SloSpec(ttft_s=0.5)
    assert parse_slo("tpot:0.05,ttft:0.5") == SloSpec(ttft_s=0.5,
                                                      tpot_s=0.05)
    for bad in ("ttft:x", "ttft:0.5,ttft:1", "p99:1"):
        with pytest.raises(ValueError):
            parse_slo(bad)
    monkeypatch.delenv(ENV_SLO_TTFT, raising=False)
    monkeypatch.delenv(ENV_SLO_TPOT, raising=False)
    assert parse_slo() is None                     # default: no SLO
    monkeypatch.setenv(ENV_SLO_TTFT, "0.25")
    assert parse_slo() == SloSpec(ttft_s=0.25)


# -- the virtual-clock driver on the real engine -----------------------------

_ENGINE_KW = dict(num_slots=2, block_size=8, num_blocks=17,
                  prefill_chunk=8, max_model_len=64, timeline="off")


def _schedule(rate=50.0):
    return make_schedule(6, 128, process="poisson", rate=rate, seed=3,
                         prompt_lo=4, prompt_hi=10, new_lo=3, new_hi=6,
                         eos_token_id=127, groups=("a", "b"))


def _drive(model, params, schedule, slo, out_dir=None, target="engine",
           rate=None):
    """One virtual-clock open-loop run on a fresh target; returns
    (outputs-in-submission-order, driver summary, raw serve events)."""
    from huggingface_sagemaker_tensorflow_distributed_tpu.serve.engine import (
        ServeEngine,
    )
    from huggingface_sagemaker_tensorflow_distributed_tpu.serve.router import (
        Router,
    )

    events = []
    if out_dir is not None:
        obs.reset(out_dir=str(out_dir), enabled=True)
    try:
        if target == "engine":
            t = ServeEngine(model, params, **_ENGINE_KW)
        else:
            t = Router(model, params, replicas=1,
                       placement="round_robin", **_ENGINE_KW)
        drv = OpenLoopDriver(t, schedule, clock="virtual", tick_s=0.001,
                             slo=slo, process="poisson", rate=rate)
        finished = drv.run()
        outs = [list(finished[rid].output) for rid in sorted(finished)]
        summary = drv.summary()
        if out_dir is not None:
            obs.flush()
            for line in (out_dir / "events.jsonl").read_text(
                    encoding="utf-8").splitlines():
                rec = json.loads(line)
                if rec.get("type") == "serve":
                    events.append(rec)
    finally:
        if out_dir is not None:
            obs.reset()
    return outs, summary, events


def _normalize(events):
    """The deterministic projection of a serve event stream: event
    kinds, submission-order request indices, token payloads and the
    integer backlog rider — everything except wall-clock stamps, which
    virtual mode deliberately leaves wall-domain."""
    rids = {}
    out = []
    for e in events:
        rid = e.get("request")
        if isinstance(rid, int) and rid not in rids:
            rids[rid] = len(rids)
        row = {"event": e.get("event")}
        if isinstance(rid, int):
            row["request"] = rids[rid]
        for k in ("token", "tokens", "arrival_backlog", "requests",
                  "process", "clock", "rate"):
            if k in e:
                row[k] = e[k]
        out.append(row)
    return out


def test_virtual_replay_is_byte_identical(gpt2_setup, tmp_path):
    """Same seed + schedule => token-identical outputs, byte-identical
    driver summaries, and identical normalized event streams — across
    reruns AND across Router(replicas=1) vs the bare engine (the
    passthrough contract)."""
    _, model, params = gpt2_setup
    slo = SloSpec(ttft_s=0.02, tpot_s=0.01)
    runs = [
        _drive(model, params, _schedule(), slo, tmp_path / "a",
               target="engine", rate=50.0),
        _drive(model, params, _schedule(), slo, tmp_path / "b",
               target="engine", rate=50.0),
        _drive(model, params, _schedule(), slo, tmp_path / "c",
               target="router", rate=50.0),
    ]
    outs0, sum0, ev0 = runs[0]
    assert all(len(o) > 0 for o in outs0)
    assert sum0["slo_attainment"] == 1.0           # underload holds
    assert sum0["clock"] == "virtual"
    for outs, summary, events in runs[1:]:
        assert outs == outs0
        assert (json.dumps(summary, sort_keys=True)
                == json.dumps(sum0, sort_keys=True))
        assert _normalize(events) == _normalize(ev0)
    # the open_loop stamp leads each stream, and every submit carries
    # its arrival stamp (the backlog ledger rider needs timeline="on";
    # the schema fixtures in test_obsctl cover that shape)
    assert ev0[0]["event"] == "open_loop"
    assert ev0[0]["process"] == "poisson" and ev0[0]["requests"] == 6
    assert all("arrival_s" in e for e in ev0 if e["event"] == "submit")


def test_virtual_overload_is_queue_dominant(gpt2_setup):
    """At a rate far past fleet capacity the driver's verdict must be
    the open-loop signature: attainment strictly below 1 with QUEUE the
    dominant miss phase, and the engine's deterministic backlog peak
    above the underload run's, with fewer deadline-meeting tokens than
    that run (the same requests at 50 a second under the same SLO)."""
    from huggingface_sagemaker_tensorflow_distributed_tpu.serve.engine import (
        ServeEngine,
    )

    _, model, params = gpt2_setup
    eng = ServeEngine(model, params, **_ENGINE_KW)
    drv = OpenLoopDriver(eng, _schedule(rate=100000.0), clock="virtual",
                         tick_s=0.001, slo=SloSpec(ttft_s=0.003),
                         process="poisson", rate=100000.0)
    drv.run()
    s = drv.summary()
    assert 0.0 < s["slo_attainment"] < 1.0
    assert s["dominant_miss_phase"] == "queue"
    assert s["miss_phases"]["queue"] == s["slo_missed"]
    assert set(s["group_slo_attainment"]) == {"a", "b"}
    calm_eng = ServeEngine(model, params, **_ENGINE_KW)
    calm = OpenLoopDriver(calm_eng, _schedule(), clock="virtual",
                          tick_s=0.001, slo=SloSpec(ttft_s=0.003),
                          process="poisson", rate=50.0)
    calm.run()
    assert (eng.slo_summary()["arrival_backlog_peak"]
            > calm_eng.slo_summary()["arrival_backlog_peak"])
    assert 0 < s["goodput_tokens"] < calm.summary()["goodput_tokens"]


def test_driver_is_one_shot(gpt2_setup):
    _, model, params = gpt2_setup
    from huggingface_sagemaker_tensorflow_distributed_tpu.serve.engine import (
        ServeEngine,
    )

    eng = ServeEngine(model, params, **_ENGINE_KW)
    drv = OpenLoopDriver(eng, _schedule(), clock="virtual")
    drv.run()
    with pytest.raises(RuntimeError):
        drv.run()
    with pytest.raises(ValueError):
        OpenLoopDriver(eng, _schedule(), clock="sundial")
    with pytest.raises(ValueError):
        OpenLoopDriver(eng, _schedule(), tick_s=0.0)


# -- the engine's SLO contract -----------------------------------------------

def test_closed_loop_stream_has_no_new_fields(gpt2_setup, tmp_path):
    """Absent-when-default: a plain closed-loop run (no arrival_s, no
    slo) must emit a stream with NONE of the ISSUE 16 fields — the
    byte-identity contract for every pre-16 consumer."""
    from huggingface_sagemaker_tensorflow_distributed_tpu.serve.engine import (
        ServeEngine,
    )

    out = tmp_path / "closed"
    obs.reset(out_dir=str(out), enabled=True)
    try:
        eng = ServeEngine(model=gpt2_setup[1], params=gpt2_setup[2],
                          **_ENGINE_KW)
        for _, spec in _schedule():
            eng.submit(spec["prompt"], spec["max_new_tokens"])
        eng.run()
        assert "slo_attainment" not in eng.slo_summary()
        assert "arrival_backlog_peak" not in eng.slo_summary()
        obs.flush()
    finally:
        obs.reset()
    new_fields = {"arrival_s", "slo_ttft_s", "slo_tpot_s", "slo_met",
                  "ttft_slo_met", "tpot_slo_met", "slack_s",
                  "slo_attainment", "group_slo_attainment",
                  "arrival_backlog", "arrival_backlog_peak"}
    for line in (out / "events.jsonl").read_text(
            encoding="utf-8").splitlines():
        rec = json.loads(line)
        if rec.get("type") == "serve":
            assert not new_fields & set(rec), rec


def test_wall_slo_verdicts_ride_the_stream(gpt2_setup, tmp_path):
    """slo= threaded into submit: finish events carry the verdict
    (slo_met / per-axis flags / slack), the report event the
    attainment + per-group split, and ledgers the arrival backlog."""
    from huggingface_sagemaker_tensorflow_distributed_tpu.serve.engine import (
        ServeEngine,
    )

    out = tmp_path / "wall"
    obs.reset(out_dir=str(out), enabled=True)
    try:
        eng = ServeEngine(model=gpt2_setup[1], params=gpt2_setup[2],
                          **_ENGINE_KW)
        drv = OpenLoopDriver(eng, _schedule(rate=200.0), clock="wall",
                             slo=SloSpec(ttft_s=5.0, tpot_s=5.0),
                             process="poisson", rate=200.0)
        drv.run()
        assert eng.slo_summary()["slo_attainment"] == 1.0
        assert set(eng.slo_summary()["group_slo_attainment"]) == \
            {"a", "b"}
        obs.flush()
    finally:
        obs.reset()
    finishes = reports = submits = 0
    for line in (out / "events.jsonl").read_text(
            encoding="utf-8").splitlines():
        rec = json.loads(line)
        if rec.get("type") != "serve":
            continue
        if rec.get("event") == "finish":
            finishes += 1
            assert rec["slo_met"] is True
            assert rec["ttft_slo_met"] is True
            assert rec["tpot_slo_met"] is True
            assert rec["slack_s"] > 0
        elif rec.get("event") == "report":
            reports += 1
            assert rec["slo_attainment"] == 1.0
            assert "arrival_backlog_peak" in rec
        elif rec.get("event") == "submit":
            submits += 1
            assert rec["arrival_s"] > 0
            assert rec["slo_ttft_s"] == 5.0
    assert finishes == 6 and submits == 6 and reports == 1
