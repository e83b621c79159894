"""The serving engine's host loop and warm-up measured from inside
(ISSUE 25): every iteration's account of its own wall time, ``obs``
spans on the profiler's clock and written in batches, life-cycle spans
that wait for a sink, and the host-pause meter."""

import threading
import time

import numpy as np
import pytest

from huggingface_sagemaker_tensorflow_distributed_tpu import obs
from huggingface_sagemaker_tensorflow_distributed_tpu.obs.core import NULL_SPAN
from huggingface_sagemaker_tensorflow_distributed_tpu.obs.watchdog import (
    PauseMeter,
)

PARTS = ("stage_s", "dispatch_s", "fetch_wait_s", "commit_s")
GEOMETRY = dict(num_slots=2, block_size=4, num_blocks=40, prefill_chunk=8,
                max_model_len=64)


def _gpt2(layers: int, seed: int):
    import jax.numpy as jnp

    from huggingface_sagemaker_tensorflow_distributed_tpu.models.auto import (
        init_params,
    )
    from huggingface_sagemaker_tensorflow_distributed_tpu.models.gpt2 import (
        Gpt2Config,
        Gpt2LMHeadModel,
    )

    cfg = Gpt2Config(vocab_size=128, hidden_size=32, num_layers=layers,
                     num_heads=2, intermediate_size=64,
                     max_position_embeddings=128, hidden_dropout=0.0,
                     embd_dropout=0.0, attention_dropout=0.0,
                     eos_token_id=127, pad_token_id=0, dtype=jnp.float32)
    model = Gpt2LMHeadModel(cfg)
    return model, init_params(model, cfg, seed=seed)


@pytest.fixture(scope="module")
def target():
    return _gpt2(2, 0)


@pytest.fixture(scope="module")
def draft():
    return _gpt2(1, 5)


def _engine(target, **kw):
    from huggingface_sagemaker_tensorflow_distributed_tpu.serve.engine import (
        ServeEngine,
    )

    model, params = target
    eng = ServeEngine(model, params, **GEOMETRY, **kw)
    rng = np.random.RandomState(4)
    for n in (5, 11, 7, 9):       # four requests on two slots: a queue
        eng.submit(rng.randint(1, 120, (n,)).astype(np.int32), 6)
    return eng


def _events(out):
    path = out / "events.jsonl"
    if not path.exists():
        return []
    return [e for _, e, err in obs.iter_events(str(path)) if err is None]


def _ledger(events):
    return [e for e in events if e["type"] == "serve"
            and e["event"] == "iteration_ledger"]


MODES = {"overlap-on": dict(overlap="on"), "overlap-off": dict(overlap="off"),
         "speculative": dict(speculate_k=2)}


@pytest.fixture(scope="module", params=sorted(MODES))
def served(request, target, draft, tmp_path_factory):
    """One tiny engine run to its end with a sink: the ledger lines it
    wrote and the engine's own totals."""
    out = tmp_path_factory.mktemp("telemetry")
    kw = dict(MODES[request.param])
    if "speculate_k" in kw:
        kw["draft"] = draft
    obs.reset(out_dir=str(out), enabled=True)
    try:
        eng = _engine(target, **kw)
        eng.run()
        totals = eng.host_loop_totals()
    finally:
        obs.reset()
    return _events(out), totals


def test_every_ledger_line_accounts_for_its_wall_time(served):
    lines = _ledger(served[0])
    assert len(lines) >= 8
    for e in lines:
        # every field is rounded to a microsecond
        assert sum(e[p] for p in PARTS) <= e["dur_s"] + 5e-6, e
        assert e["gap_s"] >= 0 and all(e[p] >= 0 for p in PARTS)
        assert isinstance(e["preemptions"], int)
    assert lines[0]["gap_s"] == 0          # nothing returned before it
    assert sum(e["fetch_wait_s"] for e in lines) > 0
    assert sum(e["dispatch_s"] for e in lines) > 0


def test_engine_totals_are_the_sums_of_its_ledger_lines(served):
    events, totals = served
    lines = _ledger(events)
    assert totals["iterations"] == len(lines)
    for key in PARTS + ("dur_s", "gap_s"):
        assert totals[key] == pytest.approx(
            sum(e[key] for e in lines), abs=1e-6 * len(lines)), key
    assert sum(totals[p] for p in PARTS) <= totals["dur_s"]


def test_ledger_carries_the_gauges_and_the_series_stay_with_timeline_off(
        target, tmp_path):
    gauges = {"serve/waiting_depth", "serve/running_slots",
              "serve/preemptions", "serve/gather_bucket"}
    names = {}
    for timeline in ("on", "off"):
        out = tmp_path / timeline
        obs.reset(out_dir=str(out), enabled=True)
        try:
            _engine(target, timeline=timeline).run()
        finally:
            obs.reset()
        events = _events(out)
        names[timeline] = ({e["name"] for e in events
                            if e["type"] == "metric"}, _ledger(events))
    on_metrics, on_ledger = names["on"]
    off_metrics, off_ledger = names["off"]
    assert on_ledger and not (gauges & on_metrics)
    assert {"waiting", "preemptions", "gather_bucket",
            "decode_slots"} <= set(on_ledger[0])
    assert not off_ledger and gauges <= off_metrics


def test_program_spans_are_in_a_profiler_trace_nested(target, tmp_path):
    """A CPU ``jax.profiler`` trace of three engine iterations holds
    ``hstd/serve/step`` with ``hstd/serve/commit_fetch`` inside it, on
    the host plane."""
    import glob

    import jax
    from jax.profiler import ProfileData

    obs.reset(out_dir=str(tmp_path / "telemetry"), enabled=True)
    try:
        eng = _engine(target)
        eng.warmup()
        for _ in range(4):              # past admission and prefill
            eng.step()
        jax.profiler.start_trace(str(tmp_path / "trace"))
        try:
            for _ in range(3):
                eng.step()
        finally:
            jax.profiler.stop_trace()
    finally:
        obs.reset()
    path, = glob.glob(str(tmp_path / "trace" / "plugins" / "profile" / "*"
                          / "*.xplane.pb"))
    spans = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("hstd/"):
                    spans.setdefault(ev.name, []).append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns))
    assert len(spans["hstd/serve/step"]) == 3
    assert spans["hstd/serve/commit_fetch"]
    for s, e in spans["hstd/serve/commit_fetch"]:
        assert any(s0 <= s and e <= e0
                   for s0, e0 in spans["hstd/serve/step"])
    assert {"hstd/serve/admit", "hstd/serve/stage_decode",
            "hstd/serve/decode_step", "hstd/serve/commit"} <= set(spans)


def test_lifecycle_spans_before_configure_are_replayed(tmp_path):
    out = tmp_path / "telemetry"
    state = obs.reset(enabled=True)             # no directory yet
    try:
        with obs.lifecycle_span("serve/warmup"):
            with obs.lifecycle_span("serve/warmup/prefill_g1"):
                pass
        assert obs.span("serve/step") is NULL_SPAN
        kept = {r[0]: r for r in state._lifecycle}
        assert set(kept) == {"serve/warmup", "serve/warmup/prefill_g1"}
        assert state.spans == [] and not out.exists()
        obs.configure(out_dir=str(out))
        assert state._lifecycle == []
        obs.flush()
        spans = {e["name"]: e for e in _events(out) if e["type"] == "span"}
        for name, record in kept.items():
            assert spans[name]["mono"] == round(record[1], 9)
            assert spans[name]["dur"] == round(record[2], 9)
        assert spans["serve/warmup/prefill_g1"]["parent"] == "serve/warmup"
        count, errors = obs.validate_events_file(str(out / "events.jsonl"))
        assert errors == [] and count >= 3
    finally:
        obs.reset()


def test_lifecycle_buffer_is_bounded_and_off_when_disabled():
    state = obs.reset(enabled=True)
    try:
        for _ in range(300):
            with obs.lifecycle_span("serve/warmup"):
                pass
        assert len(state._lifecycle) == 256
        obs.configure(enabled=False)
        assert obs.lifecycle_span("serve/warmup") is NULL_SPAN
    finally:
        obs.reset()


def test_spans_are_written_at_shutdown_and_none_before(tmp_path):
    out = tmp_path / "telemetry"
    obs.reset(out_dir=str(out), enabled=True)
    try:
        obs.scalar("a", 1.0)                    # opens the file
        for i in range(5):
            with obs.span("serve/step", {"iteration": i}):
                pass
        assert [e for e in _events(out) if e["type"] == "span"] == []
        assert len(obs.state()._pending) == 5
        before = time.time()
        obs.shutdown()
        spans = [e for e in _events(out) if e["type"] == "span"]
        assert [e["args"]["iteration"] for e in spans] == list(range(5))
        monos = [e["mono"] for e in spans]
        assert monos == sorted(monos)
        # the envelope's `t` is when the batch was written
        assert all(e["t"] >= before for e in spans)
    finally:
        obs.reset()


def test_a_full_batch_of_spans_is_written_without_a_flush(tmp_path,
                                                         monkeypatch):
    from huggingface_sagemaker_tensorflow_distributed_tpu.obs import core

    monkeypatch.setattr(core, "_SPAN_BATCH", 4)
    out = tmp_path / "telemetry"
    obs.reset(out_dir=str(out), enabled=True)
    try:
        for _ in range(9):
            with obs.span("s"):
                pass
        assert len([e for e in _events(out) if e["type"] == "span"]) == 8
        assert len(obs.state()._pending) == 1
    finally:
        obs.reset()


class _Clock:
    """A clock that a fake ``sleep`` advances; ``late`` is added to the
    sleeps whose turn it names."""

    def __init__(self, late: dict):
        self.now, self.sleeps, self.late = 100.0, 0, late

    def __call__(self) -> float:
        return self.now

    def sleep(self, seconds: float) -> None:
        self.now += seconds + self.late.get(self.sleeps, 0.0)
        self.sleeps += 1


def test_pause_meter_reports_a_late_wake_up_and_nothing_on_time(tmp_path):
    out = tmp_path / "telemetry"
    state = obs.reset(out_dir=str(out), enabled=True)
    try:
        clock = _Clock({3: 0.300, 5: 0.015})    # the 4th wake-up is late
        meter = PauseMeter(state, clock=clock, sleep=clock.sleep)
        for _ in range(8):
            meter.cycle()
        meter.stop()
        events = _events(out)
        pauses = [e for e in events if e["type"] == "host_pause"]
        assert len(pauses) == 1
        assert pauses[0]["dur"] == pytest.approx(0.300, abs=1e-6)
        # due after three on-time sleeps and its own interval
        assert pauses[0]["mono"] == pytest.approx(
            100.0 + 4 * 0.010 - state.mono0, abs=1e-5)
        worst = [e for e in events if e["type"] == "metric"
                 and e["name"] == "host/pause_max_s"]
        assert len(worst) == 1
        assert worst[0]["value"] == pytest.approx(0.300, abs=1e-6)
        assert all(obs.validate_event(e) == [] for e in events)
    finally:
        obs.reset()


def test_pause_meter_keeps_the_longest_only(tmp_path):
    state = obs.reset(out_dir=str(tmp_path / "t"), enabled=True)
    try:
        clock = _Clock({i: 0.030 + 0.001 * i for i in range(10)})
        meter = PauseMeter(state, keep=4, clock=clock, sleep=clock.sleep)
        for _ in range(10):
            meter.cycle()
        meter.stop()
        pauses = [e for e in _events(tmp_path / "t")
                  if e["type"] == "host_pause"]
        assert sorted(round(e["dur"], 3) for e in pauses) == [
            0.036, 0.037, 0.038, 0.039]
        assert [e["mono"] for e in pauses] == sorted(
            e["mono"] for e in pauses)
    finally:
        obs.reset()


def test_pause_meter_runs_while_a_directory_is_configured(tmp_path):
    def ticking():
        return [t for t in threading.enumerate()
                if t.name == "hstd-pause-meter"]

    obs.reset(enabled=True)
    assert ticking() == []
    obs.configure(out_dir=str(tmp_path / "t"))
    try:
        assert len(ticking()) == 1
        obs.configure(out_dir=str(tmp_path / "t"))      # idempotent
        assert len(ticking()) == 1
    finally:
        obs.shutdown()
    assert ticking() == []
    names = {e.get("name") for e in _events(tmp_path / "t")
             if e["type"] == "metric"}
    assert "host/pause_max_s" in names
    obs.reset()


def test_uninstrumented_engine_keeps_totals_and_nothing_else(target,
                                                             tmp_path,
                                                             monkeypatch):
    """No directory: ``obs.span`` is the shared null span, no thread, no
    file, no span record beyond warm-up's life-cycle spans; the engine's
    per-iteration additions are its own float sums."""
    monkeypatch.chdir(tmp_path)
    state = obs.reset(enabled=True)
    try:
        assert obs.span("serve/step") is NULL_SPAN
        eng = _engine(target)
        eng.run()
        totals = eng.host_loop_totals()
        assert not [t for t in threading.enumerate()
                    if t.name == "hstd-pause-meter"]
        assert state.spans == [] and state._pending == []
        assert {r[0] for r in state._lifecycle} >= {
            "serve/warmup", "serve/warmup/prefill_g1"}
        assert all(r[0].startswith("serve/warmup")
                   for r in state._lifecycle)
        assert list(tmp_path.iterdir()) == []
    finally:
        obs.reset()
    assert totals["iterations"] == eng.iterations > 8
    assert 0 < sum(totals[p] for p in PARTS) <= totals["dur_s"]
    assert totals["fetch_wait_s"] > 0 and totals["gap_s"] >= 0
    # one list of four floats, reused: an iteration allocates no
    # container for its account
    parts = eng._iter_parts
    eng.submit(np.arange(1, 6, dtype=np.int32), 3)
    eng.step()
    assert eng._iter_parts is parts and len(parts) == 4


@pytest.mark.parametrize("event, field, bad", [
    ({"type": "host_pause", "mono": 1.0, "dur": 0.3}, "dur", "long"),
    ({"type": "host_pause", "mono": 1.0, "dur": 0.3}, "mono", None),
    ({"type": "serve", "event": "iteration_ledger", "stage_s": 0.001},
     "stage_s", "0.001"),
    ({"type": "serve", "event": "iteration_ledger", "fetch_wait_s": 0.05},
     "fetch_wait_s", [0.05]),
    ({"type": "span", "name": "s", "dur": 0.1, "mono": 1.0, "tid": 1,
      "parent": "p"}, "parent", 3),
])
def test_schema_types_the_new_events_and_fields(event, field, bad):
    good = {"v": 1, "t": 1.0, "host": 0, "pid": 1, **event}
    assert obs.validate_event(good) == []
    assert obs.validate_event({**good, field: bad}) != []
