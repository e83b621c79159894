"""bf16 training-quality evidence (SURVEY.md §7 hard-part 5): the
default TPU compute dtype must not cost accuracy.

Trains the synthetic seq-cls config twice from the same init — fp32
compute vs bf16 compute (params/optimizer state stay fp32 in both, the
framework default) — and asserts the final train accuracy lands within
2 points and eval accuracy within 3.

Why this holds (the fp32 islands that make bf16 safe here):
- attention logits + softmax in fp32 on every path — xla
  (``ops/attention.py:34``), Pallas flash (fp32 logits and
  running-max/sum scratch, ``ops/pallas_attention.py``), ring;
- layernorm statistics in fp32 (``models/layers.py::_layernorm``);
- loss, metrics, and the cross-entropy logits cast up to fp32
  (``train/trainer.py:72-75``);
- Adam moments and params in fp32 (``param_dtype``), so bf16 touches
  only activations/matmuls — the MXU-native part.
"""

import jax.numpy as jnp

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

SEQ = 32
VOCAB = 512


def _run(dtype: str, devices):
    mesh = build_mesh(MeshConfig(), devices=devices)
    enc = EncoderConfig(vocab_size=VOCAB, hidden_size=32, num_layers=2,
                        num_heads=2, intermediate_size=64,
                        max_position_embeddings=SEQ,
                        dtype=jnp.bfloat16 if dtype == "bfloat16" else jnp.float32)
    model = BertForSequenceClassification(enc, num_labels=2)
    params = init_params(model, enc, seed=0)
    cfg = TrainConfig(epochs=3, dtype=dtype, learning_rate=1e-3,
                      scale_lr_by_world_size=False, log_every_steps=0)
    trainer = Trainer(cfg, model, params, mesh)
    tok = WordHashTokenizer(vocab_size=VOCAB)
    texts, labels = synthetic_text_classification(256, seed=0)
    ds = ArrayDataset.from_texts(tok, texts, labels, max_length=SEQ)
    hist = trainer.fit(ShardedBatcher(ds, 16, mesh, shuffle=True, seed=0))

    etexts, elabels = synthetic_text_classification(128, seed=1)
    eds = ArrayDataset.from_texts(tok, etexts, elabels, max_length=SEQ)
    emetrics = trainer.evaluate(
        ShardedBatcher(eds, 16, mesh, shuffle=False, seed=0,
                       drop_remainder=False))
    return (hist["sparse_categorical_accuracy"][-1],
            emetrics["eval_accuracy"])


def test_bf16_matches_fp32_accuracy(devices8):
    train32, eval32 = _run("float32", devices8[:1])
    train16, eval16 = _run("bfloat16", devices8[:1])
    # both must actually learn, and bf16 must land within 2 train-accuracy
    # points / 3 eval points of fp32
    assert train32 > 0.8 and train16 > 0.8
    assert abs(train16 - train32) <= 0.02
    assert abs(eval16 - eval32) <= 0.03


def test_lowp_adam_step_matches_fp32_adam():
    """scale_by_adam_lowp computes the identical update to optax's fp32
    Adam up to the bf16 rounding of what was STORED between steps: a few
    steps on a toy quadratic stay within bf16-mantissa tolerance, and
    the stored state really is bf16 (the memory claim)."""
    import jax
    import numpy as np
    import optax

    from huggingface_sagemaker_tensorflow_distributed_tpu.train.optim import (
        scale_by_adam_lowp,
    )

    params = {"w": jnp.asarray(np.random.RandomState(0).randn(16, 8),
                               jnp.float32)}
    ref = optax.scale_by_adam()
    lowp = scale_by_adam_lowp()
    s_ref = ref.init(params)
    s_lowp = lowp.init(params)
    assert s_lowp.mu["w"].dtype == jnp.bfloat16
    assert s_lowp.nu["w"].dtype == jnp.bfloat16
    rng = np.random.RandomState(1)
    for step in range(5):
        g = {"w": jnp.asarray(rng.randn(16, 8) * 0.1, jnp.float32)}
        u_ref, s_ref = ref.update(g, s_ref)
        u_lowp, s_lowp = lowp.update(g, s_lowp)
        assert u_lowp["w"].dtype == jnp.float32
        np.testing.assert_allclose(np.asarray(u_lowp["w"]),
                                   np.asarray(u_ref["w"]),
                                   rtol=2e-2, atol=2e-3,
                                   err_msg=f"step {step}")


def _run_state_dtype(state_dtype: str, devices):
    mesh = build_mesh(MeshConfig(), devices=devices)
    enc = EncoderConfig(vocab_size=VOCAB, hidden_size=32, num_layers=2,
                        num_heads=2, intermediate_size=64,
                        max_position_embeddings=SEQ)
    model = BertForSequenceClassification(enc, num_labels=2)
    params = init_params(model, enc, seed=0)
    cfg = TrainConfig(epochs=3, dtype="float32", learning_rate=1e-3,
                      scale_lr_by_world_size=False, log_every_steps=0,
                      optimizer_state_dtype=state_dtype)
    trainer = Trainer(cfg, model, params, mesh)
    tok = WordHashTokenizer(vocab_size=VOCAB)
    texts, labels = synthetic_text_classification(256, seed=0)
    ds = ArrayDataset.from_texts(tok, texts, labels, max_length=SEQ)
    hist = trainer.fit(ShardedBatcher(ds, 16, mesh, shuffle=True, seed=0))
    return hist["sparse_categorical_accuracy"][-1]


def test_bf16_optimizer_state_quality(devices8):
    """bf16 m/v storage (--optimizer_state_dtype bfloat16, the optimizer
    HBM halver) must train to the same place as fp32 state — the same
    2-point bar the compute-dtype test holds bf16 matmuls to."""
    acc32 = _run_state_dtype("float32", devices8[:1])
    acc16 = _run_state_dtype("bfloat16", devices8[:1])
    assert acc32 > 0.8 and acc16 > 0.8
    assert abs(acc16 - acc32) <= 0.02
