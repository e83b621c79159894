"""Anomaly plane tests (ISSUE 4): synthetic-fault injection — a NaN
loss and a forced step-time spike each produce EXACTLY ONE rate-limited
``anomaly`` event, a flight-recorder dump, and (when enabled) a
profiler trace directory; healthy runs produce ZERO anomaly events.
Plus the straggler-alert satellite, the flight ring's bound, the
FLOPs/peak table.
"""

import json
import math
import os

import pytest
import jax

from huggingface_sagemaker_tensorflow_distributed_tpu import obs
from huggingface_sagemaker_tensorflow_distributed_tpu.obs.anomaly import (
    AnomalyDetector,
    STEP_MIN_HISTORY,
)
from huggingface_sagemaker_tensorflow_distributed_tpu.obs.flight import (
    FlightRecorder,
)
from huggingface_sagemaker_tensorflow_distributed_tpu.obs import flops


@pytest.fixture()
def obs_dir(tmp_path):
    out = tmp_path / "telemetry"
    obs.reset(out_dir=str(out), enabled=True)
    yield out
    obs.reset()


def _events(out):
    path = out / "events.jsonl"
    if not path.exists():
        return []
    return [e for _, e, err in obs.iter_events(str(path)) if err is None]


def _anomalies(out):
    return [e for e in _events(out) if e["type"] == "anomaly"]


# -- synthetic faults (acceptance gate) --------------------------------------

def test_nan_loss_fires_exactly_once_with_flight_dump(obs_dir):
    det = obs.anomalies()
    for i in range(16):
        det.observe_loss(i, 0.5)          # healthy prefix fills the ring
    for i in range(16, 24):
        det.observe_loss(i, float("nan"))  # NaN persists: must NOT re-fire
    anoms = _anomalies(obs_dir)
    assert len(anoms) == 1
    ev = anoms[0]
    assert ev["name"] == "nan_loss" and ev["step"] == 16
    assert obs.validate_event(ev) == []
    # the flight dump exists, is schema-valid, and ends with the anomaly
    assert ev.get("evidence") and os.path.exists(ev["evidence"])
    count, errors = obs.validate_events_file(ev["evidence"])
    assert errors == [] and count > 0
    rows = [json.loads(ln) for ln in open(ev["evidence"])]
    assert rows[-1]["type"] == "anomaly"


def test_step_time_spike_fires_once_per_episode(obs_dir):
    det = obs.anomalies()
    for i in range(STEP_MIN_HISTORY + 4):
        det.observe_step_time(i, 0.1)
    assert det.total == 0                  # steady state: no anomalies
    det.observe_step_time(100, 3.0)        # forced spike
    anoms = _anomalies(obs_dir)
    assert [a["name"] for a in anoms] == ["step_time_spike"]
    assert anoms[0]["step"] == 100
    # cooldown: an immediate second spike does not double-report
    det.observe_step_time(101, 3.0)
    assert len(_anomalies(obs_dir)) == 1


def test_profiler_window_on_anomaly(obs_dir, monkeypatch):
    monkeypatch.setenv("HSTD_PROFILE_ON_ANOMALY", "force")
    monkeypatch.setenv("HSTD_PROFILE_SECS", "0.0")  # close on next observe
    det = AnomalyDetector(obs.state(), recorder=obs.state().ring)
    det.observe_loss(0, float("inf"))
    ev = _anomalies(obs_dir)[0]
    assert ev.get("profile_dir")
    det.observe_loss(1, 0.5)    # poll() past the window: trace closes
    det.shutdown()
    assert os.path.isdir(ev["profile_dir"])   # jax.profiler wrote the dir


def test_grad_explosion_and_nan_grad(obs_dir):
    det = obs.anomalies()
    for i in range(12):
        det.observe_grad_norm(i, 1.0)
    assert det.total == 0
    det.observe_grad_norm(20, 50.0)        # 50x the rolling median
    assert det.counts.get("grad_explosion") == 1
    det.observe_grad_norm(21, float("nan"))
    assert det.counts.get("nan_grad") == 1


def test_straggler_alert_names_slow_host(obs_dir):
    det = obs.anomalies()
    stats = {"straggler_ratio": 1.3, "argmax": 2, "n_hosts": 4}
    assert not det.observe_straggler(0, stats)       # 1st epoch: armed
    assert not det.observe_straggler(1, {**stats, "straggler_ratio": 1.05})
    assert not det.observe_straggler(2, stats)       # run was reset
    assert det.observe_straggler(3, stats)           # 2 consecutive
    ev = _anomalies(obs_dir)[0]
    assert ev["name"] == "straggler" and ev["slow_host"] == 2
    assert "host 2" in ev["message"]


def test_begin_fit_resets_rolling_baselines(obs_dir):
    det = obs.anomalies()
    for i in range(12):
        det.observe_step_time(i, 0.01)
    det.begin_fit()
    # a second fit's much slower (but steady) regime is NOT a spike —
    # the rolling baseline was reset with the new run
    for i in range(12):
        det.observe_step_time(i, 0.5)
    assert det.total == 0


def test_disabled_detector_is_inert(obs_dir, monkeypatch):
    monkeypatch.setenv("HSTD_ANOMALY", "0")
    det = AnomalyDetector(obs.state(), recorder=obs.state().ring)
    det.observe_loss(0, float("nan"))
    det.observe_step_time(0, 99.0)
    assert det.total == 0 and _anomalies(obs_dir) == []


# -- flight ring -------------------------------------------------------------

def test_flight_ring_is_bounded_and_dump_ordered(tmp_path):
    ring = FlightRecorder(capacity=8)
    for i in range(50):
        ring.record({"v": 1, "t": float(i), "host": 0, "pid": 1,
                     "type": "metric", "name": "x", "value": float(i)})
    assert len(ring) == 8
    path = ring.dump(str(tmp_path), 50)
    rows = [json.loads(ln) for ln in open(path)]
    assert [r["value"] for r in rows] == [float(i) for i in range(42, 50)]
    # a second dump for the same step keeps the first (no clobbering)
    ring.record({"v": 1, "t": 99.0, "host": 0, "pid": 1,
                 "type": "metric", "name": "y", "value": 99.0})
    assert ring.dump(str(tmp_path), 50) == path
    assert len([json.loads(ln) for ln in open(path)]) == 8


# -- FLOPs / peak table ------------------------------------------------------

def test_peak_tflops_table_and_override(monkeypatch):
    assert flops.peak_tflops("TPU v5 lite") == 197.0
    assert flops.peak_tflops("TPU v4") == 275.0
    assert flops.peak_tflops("Intel Xeon") is None
    monkeypatch.setenv(flops.ENV_PEAK, "2.5")
    assert flops.peak_tflops("Intel Xeon") == 2.5    # override wins
    assert flops.peak_tflops("TPU v4") == 2.5
    monkeypatch.setenv(flops.ENV_PEAK, "bogus")
    assert flops.peak_tflops("Intel Xeon") is None


def test_train_flops_per_token_families():
    from huggingface_sagemaker_tensorflow_distributed_tpu.models.gpt2 import (
        Gpt2Config,
    )
    from huggingface_sagemaker_tensorflow_distributed_tpu.models.llama import (
        LlamaConfig,
    )

    gpt2 = Gpt2Config()      # 124M: 12L/768H/3072FFN/50257V
    f = flops.train_flops_per_token(gpt2, "causal-lm", 512)
    # 3x(12*(8*768^2 + 4*512*768 + 4*768*3072) + 2*768*50257)
    assert f == pytest.approx(3 * (12 * (8 * 768**2 + 4 * 512 * 768
                                         + 4 * 768 * 3072)
                                   + 2 * 768 * 50257))
    # llama family: gated MLP (3 matmuls) + GQA-scaled kv projections
    llama = LlamaConfig(vocab_size=1000, hidden_size=64, num_layers=2,
                        num_heads=4, num_kv_heads=2, intermediate_size=128)
    f = flops.train_flops_per_token(llama, "causal-lm", 64)
    assert f == pytest.approx(3 * (2 * (2 * 64 * 64 * 3 + 4 * 64 * 64
                                        + 6 * 64 * 128) + 2 * 64 * 1000))
    # mlm pays the head only on the masked fraction
    enc = Gpt2Config()
    full = flops.train_flops_per_token(enc, "causal-lm", 512)
    mlm = flops.train_flops_per_token(enc, "mlm", 512)
    assert mlm < full
    # sparse MoE: routed surcharge applies to layers//moe_every layers
    # only (top_k-1 extra MLPs each)
    moe = LlamaConfig(vocab_size=1000, hidden_size=64, num_layers=4,
                      num_heads=4, num_kv_heads=2, intermediate_size=128,
                      num_experts=8, expert_top_k=2, moe_every=2)
    dense_f = flops.train_flops_per_token(
        LlamaConfig(vocab_size=1000, hidden_size=64, num_layers=4,
                    num_heads=4, num_kv_heads=2, intermediate_size=128),
        "causal-lm", 64)
    moe_f = flops.train_flops_per_token(moe, "causal-lm", 64)
    assert moe_f == pytest.approx(dense_f + 3 * 2 * 1 * 6 * 64 * 128)
    assert flops.mfu(10.0, 100.0) == pytest.approx(0.1)
    assert flops.mfu(None, 100.0) is None and flops.mfu(10.0, None) is None


def test_trainer_flops_speaks_t5_and_bart_dialects():
    """Regression: seq2seq configs use d_model/d_ff (T5) and
    d_model/encoder_ffn_dim (BART) — the accounting must produce
    positive figures for both, and NEVER raise (a config the model
    doesn't understand degrades to (0, 0), not a crashed fit)."""
    from huggingface_sagemaker_tensorflow_distributed_tpu.models.bart import (
        BartConfig,
    )
    from huggingface_sagemaker_tensorflow_distributed_tpu.models.t5 import (
        T5Config,
    )

    for cfg in (T5Config(), BartConfig()):
        enc, dec = flops.trainer_flops_per_token(cfg, "seq2seq", 128)
        assert enc > 0 and dec > enc    # decoder adds cross-attn + head
    # T5 v1.1 gated MLP costs more than the same dims ungated
    plain = flops.trainer_flops_per_token(T5Config(), "seq2seq", 128)
    gated = flops.trainer_flops_per_token(
        T5Config(feed_forward_proj="gated-gelu"), "seq2seq", 128)
    assert gated[0] > plain[0]
    # junk config: degrade, don't raise

    class Junk:
        pass

    assert flops.trainer_flops_per_token(Junk(), "seq2seq", 128) == (0.0,
                                                                     0.0)
    assert flops.trainer_flops_per_token(None, "causal-lm", 128) == (0.0,
                                                                     0.0)


def test_flight_dump_schema_valid_without_event_log(tmp_path, monkeypatch):
    """Regression: a host that owns no event log (rank != 0) must still
    write an envelope-stamped, schema-valid flight dump."""
    obs.reset(out_dir=str(tmp_path / "t"), enabled=True)
    try:
        obs.set_host(1, 2)            # demoted: events.jsonl closed
        assert not obs.has_sink()
        det = obs.anomalies()
        det.observe_loss(5, float("nan"))
        flights = [f for f in os.listdir(tmp_path / "t")
                   if f.startswith("flight_")]
        assert flights
        count, errors = obs.validate_events_file(
            str(tmp_path / "t" / flights[0]))
        assert errors == [] and count == 1
        rows = [json.loads(ln)
                for ln in open(tmp_path / "t" / flights[0])]
        assert rows[-1]["host"] == 1 and rows[-1]["type"] == "anomaly"
    finally:
        obs.reset()


# -- end-to-end: trainer fault injection -------------------------------------

def _fit(tmp_path, lr, n=48, log_every=1):
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
                      learning_rate=lr, scale_lr_by_world_size=False,
                      output_data_dir=str(tmp_path),
                      log_every_steps=log_every)
    mesh = build_mesh(MeshConfig())
    model, params = _tiny_model()
    trainer = Trainer(cfg, model, params, mesh)
    batcher = ShardedBatcher(_data(n=n), 16, mesh, shuffle=False, seed=0)
    return trainer.fit(batcher)


def test_healthy_fit_emits_zero_anomalies_and_mfu(obs_dir, tmp_path,
                                                  monkeypatch):
    monkeypatch.setenv(flops.ENV_PEAK, "0.5")
    hist = _fit(tmp_path, lr=1e-3)
    assert _anomalies(obs_dir) == []
    assert not [f for f in os.listdir(obs_dir)
                if f.startswith("flight_")]
    # MFU accounting flowed through: history figure + metric series
    assert 0 < hist["train_mfu"] <= 1.0
    names = {e.get("name") for e in _events(obs_dir)
             if e["type"] == "metric"}
    assert {"train/mfu", "train/step_time_s", "train/model_flops",
            "train/achieved_tflops_per_chip"} <= names


def test_nan_loss_fit_triggers_anomaly_and_flight_dump(obs_dir, tmp_path):
    # lr large enough to overflow float32 params in one update: the
    # next step's loss is non-finite — the divergence CI must catch
    hist = _fit(tmp_path, lr=1e32)
    assert any(not math.isfinite(loss) for loss in hist["loss"])
    anoms = _anomalies(obs_dir)
    kinds = {a["name"] for a in anoms}
    assert kinds & {"nan_loss", "nan_grad"}
    assert len([a for a in anoms if a["name"] == "nan_loss"]) <= 1
    assert [f for f in os.listdir(obs_dir) if f.startswith("flight_")]
    for a in anoms:
        assert obs.validate_event(a) == []
