"""Pallas fused-attention numerics vs the XLA reference implementation
(interpret mode on CPU; the same kernel runs compiled on TPU)."""

import jax
import jax.numpy as jnp
import numpy as np

from huggingface_sagemaker_tensorflow_distributed_tpu.ops.attention import (
    make_attention_mask,
    xla_attention,
)
from huggingface_sagemaker_tensorflow_distributed_tpu.ops.pallas_attention import (
    flash_attention,
)


def _qkv(b=2, h=2, s=64, d=32, seed=0, dtype=jnp.float32):
    r = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(r.normal(size=(b, h, s, d)), dtype)
    return mk(), mk(), mk()


def test_matches_xla_no_mask():
    q, k, v = _qkv()
    out = flash_attention(q, k, v, block_q=32, interpret=True)
    ref = xla_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_matches_xla_with_padding_mask():
    q, k, v = _qkv(seed=1)
    pad = np.ones((2, 64), np.int32)
    pad[0, 40:] = 0
    pad[1, 10:] = 0
    mask = make_attention_mask(jnp.asarray(pad))
    out = flash_attention(q, k, v, mask=mask, block_q=32, interpret=True)
    ref = xla_attention(q, k, v, mask=mask)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_bf16_inputs():
    q, k, v = _qkv(seed=2, dtype=jnp.bfloat16)
    out = flash_attention(q, k, v, block_q=32, interpret=True)
    ref = xla_attention(q, k, v)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref, np.float32),
                               atol=2e-2)


def test_fallback_on_odd_lengths():
    q, k, v = _qkv(s=60)  # 60 % 32 != 0 with block 32... use block_q default
    out = flash_attention(q, k, v, block_q=64, interpret=True)
    ref = xla_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_fallback_on_general_mask():
    q, k, v = _qkv(seed=3)
    full = jnp.zeros((2, 2, 64, 64))
    out = flash_attention(q, k, v, mask=full, interpret=True)
    ref = xla_attention(q, k, v, mask=full)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_flash_qkv_grads_match_xla():
    """The fused Pallas backward (dQ / dK-dV kernels) against XLA autodiff."""
    import jax

    q, k, v = _qkv(s=256, d=32, seed=4)
    pad = np.ones((2, 256), np.int32)
    pad[0, 200:] = 0
    mask = make_attention_mask(jnp.asarray(pad))

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) ** 2)

    gf = jax.grad(loss(lambda q, k, v: flash_attention(
        q, k, v, mask, block_q=64, block_k=64, interpret=True)),
        argnums=(0, 1, 2))(q, k, v)
    gx = jax.grad(loss(lambda q, k, v: xla_attention(q, k, v, mask=mask)),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gx):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4)


def test_flash_causal_matches_xla_fwd_and_bwd():
    import jax

    from huggingface_sagemaker_tensorflow_distributed_tpu.ops.attention import (
        make_causal_mask,
    )

    q, k, v = _qkv(s=128, d=32, seed=5)
    out = flash_attention(q, k, v, block_q=32, block_k=32, causal=True,
                          interpret=True)
    ref = xla_attention(q, k, v, mask=make_causal_mask(128))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)

    gf = jax.grad(lambda q, k, v: jnp.sum(flash_attention(
        q, k, v, block_q=32, block_k=32, causal=True, interpret=True) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    gx = jax.grad(lambda q, k, v: jnp.sum(xla_attention(
        q, k, v, mask=make_causal_mask(128)) ** 2), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gx):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4)


def test_flash_blocked_kv_matches_whole_kv():
    """Online-softmax across kv blocks == single-block softmax."""
    q, k, v = _qkv(s=256, d=32, seed=6)
    out_blocked = flash_attention(q, k, v, block_q=64, block_k=64,
                                  interpret=True)
    out_whole = flash_attention(q, k, v, block_q=256, block_k=256,
                                interpret=True)
    np.testing.assert_allclose(np.asarray(out_blocked), np.asarray(out_whole),
                               atol=1e-5)


def test_flash_mask_gradient_nonzero():
    """The additive mask is a differentiable input (learned biases)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from huggingface_sagemaker_tensorflow_distributed_tpu.ops.attention import (
        make_attention_mask,
        xla_attention,
    )
    from huggingface_sagemaker_tensorflow_distributed_tpu.ops.pallas_attention import (
        flash_attention,
    )

    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(2, 2, 128, 16), jnp.float32)
    k = jnp.asarray(rng.randn(2, 2, 128, 16), jnp.float32)
    v = jnp.asarray(rng.randn(2, 2, 128, 16), jnp.float32)
    mask = make_attention_mask(jnp.ones((2, 128), jnp.int32)) * 0.0
    gf = jax.grad(lambda m: jnp.sum(flash_attention(q, k, v, m) ** 2))(mask)
    gx = jax.grad(lambda m: jnp.sum(xla_attention(q, k, v, m) ** 2))(mask)
    assert float(jnp.max(jnp.abs(gf))) > 0
    np.testing.assert_allclose(np.asarray(gf), np.asarray(gx), atol=1e-4)


def test_flash_sliding_window_matches_banded_xla():
    """Banded flash (causal + window): fwd and all grads must match XLA
    with an explicit band mask — at a multi-tile shape where whole tiles
    fall BELOW the band and are skipped."""
    import jax

    B, H, S, D = 2, 2, 256, 64
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(B, H, S, D), jnp.float32) * 0.1
    k = jnp.asarray(rng.randn(B, H, S, D), jnp.float32) * 0.1
    v = jnp.asarray(rng.randn(B, H, S, D), jnp.float32) * 0.1
    pad = np.zeros((B, 1, 1, S), np.float32)
    pad[0, ..., -32:] = -1e9
    pad = jnp.asarray(pad)

    for window in (48, 128):
        i = jnp.arange(S)[:, None]
        j = jnp.arange(S)[None, :]
        band = jnp.where((j <= i) & (j > i - window), 0.0,
                         -1e9)[None, None].astype(jnp.float32)

        # block 64: with window 48 every tile 2+ below the diagonal is
        # fully outside the band → exercises the tile-skip predicate
        out_f = flash_attention(q, k, v, mask=pad, causal=True,
                                window=window, block_q=64, block_k=64,
                                interpret=True)
        out_x = xla_attention(q, k, v, mask=pad + band)
        np.testing.assert_allclose(np.asarray(out_f), np.asarray(out_x),
                                   atol=2e-5, rtol=1e-4)

        def lf(q, k, v):
            return jnp.sum(flash_attention(q, k, v, mask=pad, causal=True,
                                           window=window, block_q=64,
                                           block_k=64,
                                           interpret=True) ** 2)

        def lx(q, k, v):
            return jnp.sum(xla_attention(q, k, v, mask=pad + band) ** 2)

        gf = jax.grad(lf, argnums=(0, 1, 2))(q, k, v)
        gx = jax.grad(lx, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gx):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=5e-5, rtol=1e-3)


def test_flash_under_dp_tp_mesh_through_trainer_step(devices8):
    """A Mosaic kernel cannot be partitioned by GSPMD (jax refuses to
    lower one under a multi-device jit), so flash is shard_mapped over
    the batch axes and, under tp, the heads axis. Run impl="flash"
    (interpret mode here) through the Trainer's jitted step on a
    dp=2 x tp=2 mesh: same losses as XLA attention on the same mesh,
    and replicas still identical afterwards."""
    from huggingface_sagemaker_tensorflow_distributed_tpu.config import (
        TrainConfig,
    )
    from huggingface_sagemaker_tensorflow_distributed_tpu.data import (
        ArrayDataset,
        ShardedBatcher,
        WordHashTokenizer,
    )
    from huggingface_sagemaker_tensorflow_distributed_tpu.data.sources import (
        synthetic_text_classification,
    )
    from huggingface_sagemaker_tensorflow_distributed_tpu.models.auto import (
        init_params,
    )
    from huggingface_sagemaker_tensorflow_distributed_tpu.models.bert import (
        BertForSequenceClassification,
    )
    from huggingface_sagemaker_tensorflow_distributed_tpu.models.layers import (
        EncoderConfig,
    )
    from huggingface_sagemaker_tensorflow_distributed_tpu.parallel import (
        MeshConfig,
        build_mesh,
    )
    from huggingface_sagemaker_tensorflow_distributed_tpu.train import Trainer

    seq = 32
    tok = WordHashTokenizer(vocab_size=256)
    texts, labels = synthetic_text_classification(16, seed=0)
    ds = ArrayDataset.from_texts(tok, texts, labels, max_length=seq)
    mesh = build_mesh(MeshConfig(dp=2, tp=2), devices=devices8[:4])
    losses = {}
    for impl in ("xla", "flash"):
        cfg = EncoderConfig(vocab_size=256, hidden_size=32, num_layers=1,
                            num_heads=2, intermediate_size=64,
                            max_position_embeddings=seq,
                            attention_impl=impl, hidden_dropout=0.0,
                            attention_dropout=0.0)
        model = BertForSequenceClassification(cfg, num_labels=2)
        trainer = Trainer(
            TrainConfig(dtype="float32", learning_rate=1e-3,
                        scale_lr_by_world_size=False, log_every_steps=0),
            model, init_params(model, cfg, seed=0), mesh)
        batcher = ShardedBatcher(ds, 8, mesh, shuffle=False)
        run = []
        for batch in batcher.global_arrays(0):
            trainer.state, metrics = trainer._train_step(trainer.state,
                                                         batch)
            run.append(float(jax.device_get(metrics["loss"])))
        losses[impl] = run
        assert trainer.check_replica_divergence() == 0.0
    assert len(losses["flash"]) == 2
    np.testing.assert_allclose(losses["flash"], losses["xla"], atol=1e-5)
