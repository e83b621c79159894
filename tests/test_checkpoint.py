"""Checkpoint/resume tests — the capability the reference commented out
(scripts/train.py:135-137)."""

import jax
import numpy as np

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
from huggingface_sagemaker_tensorflow_distributed_tpu.parallel import MeshConfig, build_mesh
from huggingface_sagemaker_tensorflow_distributed_tpu.train import Trainer
from huggingface_sagemaker_tensorflow_distributed_tpu.train.checkpoint import Checkpointer

SEQ = 16


def _setup(tmp_path, seed=0):
    mesh = build_mesh(MeshConfig())
    cfg = TrainConfig(dtype="float32", learning_rate=1e-3, log_every_steps=0,
                      checkpoint_dir=str(tmp_path / "ckpt"))
    mcfg = EncoderConfig(vocab_size=256, hidden_size=32, num_layers=1,
                         num_heads=2, intermediate_size=64,
                         max_position_embeddings=SEQ)
    model = BertForSequenceClassification(mcfg, num_labels=2)
    trainer = Trainer(cfg, model, init_params(model, mcfg, seed=seed), mesh)
    tok = WordHashTokenizer(vocab_size=256)
    texts, labels = synthetic_text_classification(64, seed=0)
    ds = ArrayDataset.from_texts(tok, texts, labels, max_length=SEQ)
    batcher = ShardedBatcher(ds, 16, mesh, shuffle=True, seed=0)
    return cfg, trainer, batcher


def test_save_restore_roundtrip(tmp_path):
    cfg, trainer, batcher = _setup(tmp_path)
    for batch in batcher.global_arrays(0):
        trainer.state, _ = trainer._train_step(trainer.state, batch)
    ckpt = Checkpointer(cfg.checkpoint_dir)
    ckpt.save(trainer.state, epoch=1)
    # async save: a SEPARATE manager (fresh process in real resume) only
    # sees the checkpoint once the writer finished
    ckpt.wait_until_finished()
    assert ckpt.latest_step() == 4

    # fresh trainer (different init) restores exactly
    _, trainer2, _ = _setup(tmp_path, seed=9)
    restored, epoch, step_in_epoch = Checkpointer(cfg.checkpoint_dir).restore(trainer2.state)
    assert epoch == 1 and step_in_epoch == 0
    assert int(jax.device_get(restored.step)) == 4
    a = jax.tree.leaves(jax.device_get(trainer.state.params))
    b = jax.tree.leaves(jax.device_get(restored.params))
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    ckpt.close()


def test_resume_continues_training(tmp_path):
    cfg, trainer, batcher = _setup(tmp_path)
    ckpt = Checkpointer(cfg.checkpoint_dir)
    for batch in batcher.global_arrays(0):
        trainer.state, _ = trainer._train_step(trainer.state, batch)
    ckpt.save(trainer.state, epoch=1)

    _, trainer2, batcher2 = _setup(tmp_path, seed=9)
    restored, epoch, _ = ckpt.restore(trainer2.state)
    trainer2.state = restored
    for batch in batcher2.global_arrays(epoch):
        trainer2.state, m = trainer2._train_step(trainer2.state, batch)
    assert int(jax.device_get(trainer2.state.step)) == 8
    assert np.isfinite(float(jax.device_get(m["loss"])))
    ckpt.close()


def test_mid_epoch_resume_skips_consumed_batches(tmp_path):
    """A checkpoint at step-in-epoch k must resume at batch k of the SAME
    epoch permutation — not replay the epoch (double-applied updates)."""
    cfg, trainer, batcher = _setup(tmp_path)
    ckpt = Checkpointer(cfg.checkpoint_dir)
    it = batcher.global_arrays(0)
    for _ in range(2):
        trainer.state, _ = trainer._train_step(trainer.state, next(it))
    ckpt.save(trainer.state, epoch=0, step_in_epoch=2)

    _, trainer2, batcher2 = _setup(tmp_path, seed=9)
    restored, epoch, step_in_epoch = ckpt.restore(trainer2.state)
    assert (epoch, step_in_epoch) == (0, 2)
    trainer2.state = restored
    resumed = list(batcher2.local_batches(epoch, start_step=step_in_epoch))
    full = list(batcher.local_batches(0))
    assert len(resumed) == len(full) - 2
    np.testing.assert_array_equal(resumed[0]["labels"], full[2]["labels"])
    ckpt.close()


def test_no_checkpoint_returns_none(tmp_path):
    cfg, trainer, _ = _setup(tmp_path)
    ckpt = Checkpointer(str(tmp_path / "empty"))
    assert ckpt.restore(trainer.state) is None
    ckpt.close()


def test_async_save_overlaps_and_restores_identically(tmp_path):
    """Async checkpointing: a save started during the
    step loop must commit the exact state that was passed to ``save`` —
    not a later one — and be visible to restore after the sync point."""
    cfg, trainer, batcher = _setup(tmp_path)
    ckpt = Checkpointer(cfg.checkpoint_dir, async_save=True)
    snap_params = None
    for i, batch in enumerate(batcher.global_arrays(0)):
        trainer.state, _ = trainer._train_step(trainer.state, batch)
        if i == 1:
            snap_params = jax.device_get(trainer.state.params)
            ckpt.save(trainer.state, epoch=0, step_in_epoch=i + 1)
            # keep stepping while the write is in flight
    ckpt.wait_until_finished()
    restored = ckpt.restore(trainer.state)
    assert restored is not None
    state, epoch, step_in_epoch = restored
    assert (epoch, step_in_epoch) == (0, 2)
    jax.tree.map(np.testing.assert_array_equal,
                 jax.device_get(state.params), snap_params)
    ckpt.close()


def test_divergence_check_passes_on_consistent_replicas(tmp_path):
    cfg, trainer, batcher = _setup(tmp_path)
    for batch in batcher.global_arrays(0):
        trainer.state, _ = trainer._train_step(trainer.state, batch)
    assert trainer.check_replica_divergence() == 0.0


def test_divergence_check_catches_perturbed_replica(devices8):
    """A deliberately corrupted parameter replica on ONE device must trip
    the checkpoint-boundary consistency check (SURVEY.md §5.2)."""
    import pytest

    from huggingface_sagemaker_tensorflow_distributed_tpu.parallel.collectives import (
        ReplicaDivergenceError,
    )

    mesh = build_mesh(MeshConfig(dp=-1), devices=devices8)
    cfg = TrainConfig(dtype="float32", log_every_steps=0)
    mcfg = EncoderConfig(vocab_size=64, hidden_size=16, num_layers=1,
                         num_heads=2, intermediate_size=32,
                         max_position_embeddings=SEQ)
    model = BertForSequenceClassification(mcfg, num_labels=2)
    trainer = Trainer(cfg, model, init_params(model, mcfg, seed=0), mesh)
    assert trainer.check_replica_divergence() == 0.0

    # corrupt one replica of one leaf: same sharding, device 3 disagrees
    def corrupt(leaf):
        sharding = leaf.sharding
        host = jax.device_get(leaf)
        bufs = []
        for i, d in enumerate(sharding.mesh.devices.flatten()):
            val = host + (1e-2 if i == 3 else 0.0)
            bufs.append(jax.device_put(val.astype(host.dtype), d))
        return jax.make_array_from_single_device_arrays(
            leaf.shape, sharding, bufs)

    params = trainer.state.params
    path = ("classifier", "kernel")
    leaf = params
    for p in path:
        leaf = leaf[p]
    corrupted = jax.tree_util.tree_map_with_path(
        lambda kp, x: corrupt(x)
        if tuple(getattr(k, "key", k) for k in kp) == path else x, params)
    trainer.state = trainer.state.replace(params=corrupted)
    with pytest.raises(ReplicaDivergenceError):
        trainer.check_replica_divergence()
