"""Scaling-instrument tests: the --mesh bench's trace capture + XPlane
parsing must find real collective time on a dp8 mesh (the instrument
for the ≥90% 8→32 scaling north star)."""

import numpy as np
import pytest

from benchmarks.mesh_bench import classify_event, profile_train_steps
from huggingface_sagemaker_tensorflow_distributed_tpu.config import TrainConfig
from huggingface_sagemaker_tensorflow_distributed_tpu.data import (
    ArrayDataset,
    ShardedBatcher,
    WordHashTokenizer,
)
from huggingface_sagemaker_tensorflow_distributed_tpu.data.sources import (
    synthetic_text_classification,
)
from huggingface_sagemaker_tensorflow_distributed_tpu.models.auto import init_params
from huggingface_sagemaker_tensorflow_distributed_tpu.models.bert import (
    BertForSequenceClassification,
)
from huggingface_sagemaker_tensorflow_distributed_tpu.models.layers import EncoderConfig
from huggingface_sagemaker_tensorflow_distributed_tpu.parallel import (
    MeshConfig,
    build_mesh,
)
from huggingface_sagemaker_tensorflow_distributed_tpu.train import Trainer


def test_classify_event():
    assert classify_event("all-reduce.204") == "collective"
    assert classify_event("fusion.all-gather.3") == "collective"
    assert classify_event("collective-permute-start") == "collective"
    assert classify_event("dot.1") == "compute"
    assert classify_event("wrapped_reduce") == "compute"  # not a collective
    assert classify_event("ThreadpoolListener::Record") is None
    assert classify_event("$profiler.py:246 trace") is None
    assert classify_event("end: all-reduce") == "collective"  # negligible dur


def test_profile_breakdown_finds_collectives(devices8, tmp_path):
    pytest.importorskip("tensorflow.tsl.profiler.protobuf.xplane_pb2")
    mesh = build_mesh(MeshConfig(), devices=devices8)  # dp8
    enc = EncoderConfig(vocab_size=512, hidden_size=32, num_layers=2,
                        num_heads=2, intermediate_size=64,
                        max_position_embeddings=32)
    model = BertForSequenceClassification(enc, num_labels=2)
    params = init_params(model, enc, seed=0)
    cfg = TrainConfig(dtype="float32", log_every_steps=0)
    trainer = Trainer(cfg, model, params, mesh)
    tok = WordHashTokenizer(vocab_size=512)
    texts, labels = synthetic_text_classification(64, seed=0)
    ds = ArrayDataset.from_texts(tok, texts, labels, max_length=32)
    batcher = ShardedBatcher(ds, 16, mesh, shuffle=False, seed=0)

    summary = profile_train_steps(trainer, batcher, steps=3,
                                  trace_dir=str(tmp_path))
    # dp8 gradient sync = a real all-reduce every step; device compute
    # must dominate but the collective share must be visible and sane
    assert summary["compute_ms"] > 0
    assert summary["collective_ms"] > 0
    assert 0 < summary["collective_fraction"] < 1
    assert any("all-reduce" in k for k in summary["top_collectives"])
    assert np.isfinite(summary["wall_step_ms"]) and summary["wall_step_ms"] > 0
