"""Telemetry subsystem tests (ISSUE 1): span nesting/ordering, JSONL
schema round-trip, crash-safe append, heartbeat stall dump under a
deliberately blocked thread, watchdog no-op on the CPU backend, the
zero-cost disabled path, StepMeter compile exclusion, and the
end-to-end trainer wiring (a real fit leaves schema-valid artifacts).
"""

import json
import os
import threading
import time

import numpy as np
import pytest
import jax

from huggingface_sagemaker_tensorflow_distributed_tpu import obs
from huggingface_sagemaker_tensorflow_distributed_tpu.obs.core import NULL_SPAN
from huggingface_sagemaker_tensorflow_distributed_tpu.obs.watchdog import (
    CompileTracker,
    Heartbeat,
    sample_device_memory,
)
from huggingface_sagemaker_tensorflow_distributed_tpu.utils.timing import StepMeter


@pytest.fixture()
def obs_dir(tmp_path):
    """File-backed telemetry into a fresh dir; restores the process
    default (enabled, no sink) afterwards so other tests never write."""
    out = tmp_path / "telemetry"
    obs.reset(out_dir=str(out), enabled=True)
    yield out
    obs.reset()


def _events(out):
    path = out / "events.jsonl"
    if not path.exists():
        return []  # lazy open: no file until the first event lands
    return [e for _, e, err in obs.iter_events(str(path)) if err is None]


# -- spans -------------------------------------------------------------------

def test_span_nesting_and_ordering(obs_dir):
    with obs.span("outer"):
        time.sleep(0.01)
        with obs.span("inner"):
            time.sleep(0.01)
    obs.flush()
    spans = {e["name"]: e for e in _events(obs_dir) if e["type"] == "span"}
    outer, inner = spans["outer"], spans["inner"]
    assert inner["depth"] == outer["depth"] + 1
    assert inner["parent"] == "outer" and "parent" not in outer
    # containment: inner's [start, end] inside outer's
    assert inner["mono"] >= outer["mono"]
    assert inner["mono"] + inner["dur"] <= outer["mono"] + outer["dur"] + 1e-6
    # the inner span ENDS first, so it must have been emitted first
    names = [e["name"] for e in _events(obs_dir) if e["type"] == "span"]
    assert names == ["inner", "outer"]


def test_trace_json_projection(obs_dir):
    with obs.span("a"):
        pass
    obs.flush()
    n, errors = obs.validate_trace_file(str(obs_dir / "trace.json"))
    assert n == 1 and errors == []
    doc = json.loads((obs_dir / "trace.json").read_text())
    ev = doc["traceEvents"][0]
    assert ev["ph"] == "X" and ev["name"] == "a" and ev["dur"] >= 0


# -- schema round-trip -------------------------------------------------------

def test_jsonl_schema_round_trip(obs_dir):
    obs.scalar("train/loss", 0.5, 3)
    obs.scalar("train/null_ok", None)
    with obs.span("s", {"k": 1}):
        pass
    obs.flush()
    count, errors = obs.validate_events_file(str(obs_dir / "events.jsonl"))
    assert errors == []
    assert count >= 3  # run + metric + metric + span
    metric = [e for e in _events(obs_dir)
              if e["type"] == "metric" and e["name"] == "train/loss"][0]
    assert metric["value"] == 0.5 and metric["step"] == 3
    for e in _events(obs_dir):
        assert obs.validate_event(e) == []


def test_crash_safe_append_torn_tail(obs_dir):
    obs.scalar("a", 1.0)
    obs.scalar("b", 2.0)
    path = obs_dir / "events.jsonl"
    with open(path, "a") as f:
        f.write('{"v": 1, "t": 123.0, "host": 0, "pid": 1, "type": "met')
    # the torn FINAL line (kill mid-write) is skipped, prior events read
    count, errors = obs.validate_events_file(str(path))
    assert errors == [] and count >= 3
    # torn MIDDLE line = corruption, reported
    with open(path, "a") as f:
        f.write('\n{"v": 1, "t": 1.0, "host": 0, "pid": 1, "type": '
                '"metric", "name": "c", "value": 3.0}\n')
    count2, errors2 = obs.validate_events_file(str(path))
    assert any("unparseable" in e for e in errors2)
    assert count2 == count + 1


def test_schema_rejects_bad_events():
    assert obs.validate_event([]) != []
    assert any("missing envelope" in e for e in obs.validate_event({}))
    good = {"v": 1, "t": 1.0, "host": 0, "pid": 1, "type": "metric",
            "name": "x", "value": 1.0}
    assert obs.validate_event(good) == []
    assert any("unknown event type" in e for e in obs.validate_event(
        {**good, "type": "nope"}))
    assert obs.validate_event({**good, "value": "high"}) != []
    missing = dict(good)
    del missing["name"]
    assert any("missing field 'name'" in e
               for e in obs.validate_event(missing))


# -- disabled path -----------------------------------------------------------

def test_disabled_is_allocation_free_and_writes_nothing(tmp_path):
    out = tmp_path / "t"
    obs.reset(out_dir=str(out), enabled=False)
    try:
        # the disabled span is ONE shared singleton: no per-call objects
        s1 = obs.span("train/step")
        s2 = obs.span("data/next_batch")
        assert s1 is s2 is NULL_SPAN
        with s1:
            pass
        obs.scalar("train/loss", 1.0, 0)
        obs.pulse()
        obs.flush()
        assert not (out / "events.jsonl").exists()
        assert obs.state().spans == []
    finally:
        obs.reset()


def test_env_disable(monkeypatch, tmp_path):
    monkeypatch.setenv(obs.ENV_ENABLE, "0")
    monkeypatch.setenv(obs.ENV_DIR, str(tmp_path / "x"))
    state = obs.reset()
    try:
        assert not state.enabled
        with obs.span("a"):
            pass
        assert not (tmp_path / "x" / "events.jsonl").exists()
    finally:
        monkeypatch.delenv(obs.ENV_ENABLE)
        monkeypatch.delenv(obs.ENV_DIR)
        obs.reset()


# -- heartbeat + stall dump --------------------------------------------------

def test_heartbeat_liveness_and_stall_dump(obs_dir):
    hb = Heartbeat(obs.state(), interval=0.05, stall_after=0.15,
                   sample_memory=False)
    release = threading.Event()

    def blocked_loop():
        hb.watch_current_thread()
        hb.pulse()
        release.wait(5.0)  # deliberately blocked: no pulses

    th = threading.Thread(target=blocked_loop, name="toy-train-loop")
    th.start()
    hb.start()
    try:
        deadline = time.time() + 3.0
        while hb.stall_count == 0 and time.time() < deadline:
            time.sleep(0.02)
    finally:
        release.set()
        th.join()
        hb.stop()
    assert hb.stall_count >= 1
    events = _events(obs_dir)
    assert any(e["type"] == "heartbeat" for e in events)
    stalls = [e for e in events if e["type"] == "stall"]
    assert stalls, "stall dump never fired"
    dump = stalls[0]
    # names the blocked thread and carries its stack
    assert dump["stalled"] == "toy-train-loop"
    watched = [t for t in dump["threads"] if t.get("watched")]
    assert watched and watched[0]["name"] == "toy-train-loop"
    assert any("blocked_loop" in ln for ln in watched[0]["stack"])
    assert obs.validate_event(dump) == []


def test_heartbeat_rearms_after_pulse_resumes(obs_dir):
    hb = Heartbeat(obs.state(), interval=0.04, stall_after=0.1,
                   sample_memory=False)
    hb.watch_current_thread()
    hb.start()
    try:
        time.sleep(0.3)            # first stall
        assert hb.stall_count == 1  # fires once per episode, not per beat
        hb.pulse()
        time.sleep(0.3)            # second stall episode
        assert hb.stall_count == 2
    finally:
        hb.stop()


def test_unwatch_stops_stall_detection(obs_dir):
    hb = Heartbeat(obs.state(), interval=0.04, stall_after=0.1,
                   sample_memory=False)
    hb.watch_current_thread()
    hb.unwatch()
    hb.start()
    try:
        time.sleep(0.3)
        assert hb.stall_count == 0
    finally:
        hb.stop()


# -- watchdogs on CPU --------------------------------------------------------

def test_memory_sampler_noop_on_cpu(obs_dir):
    jax.devices()  # backend initialized (CPU under JAX_PLATFORMS=cpu)
    before = len(_events(obs_dir))
    assert sample_device_memory(obs.state()) == 0
    assert len(_events(obs_dir)) == before  # no memory events emitted


def test_compile_tracker_counts_compile_events(obs_dir):
    """jax 0.9 calls duration listeners with keyword arguments
    (``fun_name=``); a backend compile is a compilation, the lowering
    stage only adds to the cumulative seconds, and a trace event (jax
    records one per nested trace and per dispatch-cache miss) is
    neither."""
    tracker = CompileTracker(obs.state())
    tracker.observe("/jax/core/compile/jaxpr_trace_duration", 9.0,
                    fun_name="step")                  # ignored
    tracker.observe(tracker.LOWERING, 0.25, fun_name="jit(step)")
    tracker.observe(tracker.BACKEND_COMPILE, 1.5, fun_name="jit(step)")
    tracker.observe(tracker.BACKEND_COMPILE, 0.25)
    assert tracker.count == 2
    assert tracker.cum_secs == pytest.approx(2.0)
    compiles = [e for e in _events(obs_dir) if e["type"] == "compile"]
    assert [c["count"] for c in compiles] == [0, 1, 2]
    assert compiles[-1]["cum"] == pytest.approx(2.0)
    for c in compiles:
        assert obs.validate_event(c) == []


def test_installed_tracker_survives_a_real_jit(obs_dir):
    """The registered listener must accept whatever the installed jax
    passes it: one real compilation counts once, and a second call of
    the same program counts nothing."""
    tracker = obs.compile_tracker()
    fn = jax.jit(lambda x: x * 3 + 1)
    x = np.ones((5,), np.float32)   # numpy in: no eager op compiles
    count0 = tracker.count
    fn(x).block_until_ready()
    assert tracker.count == count0 + 1
    fn(x).block_until_ready()
    assert tracker.count == count0 + 1


# -- StepMeter compile exclusion --------------------------------------------

def test_stepmeter_excludes_recompile_steps():
    meter = StepMeter(n_chips=1, skip_first=1)
    for recompiled in (False, True, False, False, True, False):
        meter.start_step()
        time.sleep(0.03 if recompiled else 0.001)  # compiles are slow
        meter.end_step(8, recompiled=recompiled)
    # 6 steps: first skipped + 2 recompiles excluded → 3 measured
    assert meter._measured_steps == 3
    assert meter.excluded_steps == 3
    # throughput reflects steady-state: avg step ≈ 1ms, not ~12ms
    assert meter.avg_step_time < 0.02


def test_stepmeter_window_exclusion_and_sink(tmp_path):
    class Sink:
        def __init__(self):
            self.rows = []

        def scalar(self, name, value, step=None, args=None):
            self.rows.append((name, value, step))

    sink = Sink()
    meter = StepMeter(n_chips=2, sink=sink)
    meter.begin_window()
    meter.window_step(16)
    meter.window_step(16)
    time.sleep(0.01)
    meter.end_window()
    assert meter._measured_samples == 32 and meter._measured_steps == 2
    assert sink.rows and sink.rows[0][0] == "train/samples_per_sec"
    # the trainer's recompile pattern: a compiling step is dispatched,
    # then excluded + window restarted — measured counters untouched
    meter.begin_window()
    meter.window_step(16)
    meter.exclude_step(16)
    meter.begin_window()
    meter.window_step(16)
    meter.end_window()
    assert meter._measured_samples == 48
    assert meter.excluded_steps == 1
    assert meter._steps == 4


# -- prefetch wait attribution ----------------------------------------------

def test_prefetch_wait_attribution(obs_dir):
    from huggingface_sagemaker_tensorflow_distributed_tpu.data.pipeline import (
        PrefetchIterator,
    )

    def slow_producer():
        for i in range(4):
            time.sleep(0.02)
            yield i

    it = PrefetchIterator(slow_producer(), depth=1)
    got = list(it)
    assert got == [0, 1, 2, 3]
    # consumer drained instantly → it waited on the slow producer
    assert it.stats.consumer_wait > 0.01
    waits = [e for e in _events(obs_dir) if e["type"] == "metric"
             and e["name"] == "data/consumer_wait_s"]
    assert waits and waits[0]["args"]["verdict"] == "input_bound"
    assert waits[0]["args"]["batches"] == 4


# -- end-to-end trainer wiring ----------------------------------------------

def test_trainer_fit_emits_schema_valid_telemetry(obs_dir, tmp_path):
    from tests.test_trainer import _data, _tiny_model
    from huggingface_sagemaker_tensorflow_distributed_tpu.config import (
        TrainConfig,
    )
    from huggingface_sagemaker_tensorflow_distributed_tpu.data import (
        ShardedBatcher,
    )
    from huggingface_sagemaker_tensorflow_distributed_tpu.parallel import (
        MeshConfig,
        build_mesh,
    )
    from huggingface_sagemaker_tensorflow_distributed_tpu.train import Trainer

    cfg = TrainConfig(epochs=1, train_batch_size=2, dtype="float32",
                      scale_lr_by_world_size=False,
                      output_data_dir=str(tmp_path), log_every_steps=2)
    mesh = build_mesh(MeshConfig())
    model, params = _tiny_model()
    trainer = Trainer(cfg, model, params, mesh)
    batcher = ShardedBatcher(_data(n=64), 16, mesh, shuffle=False, seed=0)
    hist = trainer.fit(batcher)
    assert hist["train_runtime"] > 0
    count, errors = obs.validate_events_file(str(obs_dir / "events.jsonl"))
    assert errors == [] and count > 0
    events = _events(obs_dir)
    names = {e.get("name") for e in events if e["type"] == "metric"}
    assert "train/loss" in names
    assert "train/samples_per_sec" in names            # meter → sink
    assert "train/step_time_hosts_mean" in names       # straggler stats
    span_names = {e["name"] for e in events if e["type"] == "span"}
    assert "train/step_dispatch" in span_names
    assert "train/sync" in span_names
    assert "xla/compile_wait" in span_names
    n_trace, trace_errors = obs.validate_trace_file(
        str(obs_dir / "trace.json"))
    assert trace_errors == [] and n_trace > 0
    stats = [e for e in events if e["type"] == "metric"
             and e["name"] == "train/step_time_hosts_mean"][0]
    assert stats["args"]["n_hosts"] == 1
    assert stats["args"]["straggler_ratio"] == 1.0


def test_trainer_disabled_telemetry_unchanged(tmp_path):
    obs.reset(enabled=False)
    try:
        from tests.test_trainer import _data, _tiny_model
        from huggingface_sagemaker_tensorflow_distributed_tpu.config import (
            TrainConfig,
        )
        from huggingface_sagemaker_tensorflow_distributed_tpu.data import (
            ShardedBatcher,
        )
        from huggingface_sagemaker_tensorflow_distributed_tpu.parallel import (
            MeshConfig,
            build_mesh,
        )
        from huggingface_sagemaker_tensorflow_distributed_tpu.train import (
            Trainer,
        )

        cfg = TrainConfig(epochs=1, train_batch_size=2, dtype="float32",
                          scale_lr_by_world_size=False,
                          output_data_dir=str(tmp_path), log_every_steps=0)
        mesh = build_mesh(MeshConfig())
        model, params = _tiny_model()
        trainer = Trainer(cfg, model, params, mesh)
        batcher = ShardedBatcher(_data(n=32), 16, mesh, shuffle=False,
                                 seed=0)
        hist = trainer.fit(batcher)
        assert hist["train_samples_per_second"] > 0
        assert obs.state().spans == []  # nothing recorded anywhere
    finally:
        obs.reset()


def test_generate_emits_tokens_per_sec(obs_dir):
    from huggingface_sagemaker_tensorflow_distributed_tpu.models.generate import (
        generate_causal,
    )
    from huggingface_sagemaker_tensorflow_distributed_tpu.models.auto import (
        init_params,
    )
    from huggingface_sagemaker_tensorflow_distributed_tpu.models.gpt2 import (
        Gpt2Config,
        Gpt2LMHeadModel,
    )

    cfg = Gpt2Config(vocab_size=128, hidden_size=16, num_layers=1,
                     num_heads=2, intermediate_size=32,
                     max_position_embeddings=64, hidden_dropout=0.0,
                     embd_dropout=0.0, attention_dropout=0.0)
    model = Gpt2LMHeadModel(cfg)
    params = init_params(model, cfg, seed=0)
    prompts = np.ones((2, 4), np.int32)
    out = generate_causal(model, params, prompts, max_new_tokens=4)
    assert out.shape == (2, 4)
    events = _events(obs_dir)
    toks = [e for e in events if e["type"] == "metric"
            and e["name"] == "generate/causal/tokens_per_sec"]
    assert toks and toks[0]["value"] > 0
    assert toks[0]["args"] == {"batch": 2, "new_tokens": 4}
