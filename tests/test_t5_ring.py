"""T5 + sequence parallelism: the encoder's
relative-bias attention must run the ring path on an sp mesh and match
the XLA path exactly — forward, loss, and gradients."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from huggingface_sagemaker_tensorflow_distributed_tpu.models import auto as auto_models
from huggingface_sagemaker_tensorflow_distributed_tpu.models import t5 as t5_mod
from huggingface_sagemaker_tensorflow_distributed_tpu.parallel import (
    MeshConfig,
    build_mesh,
    use_mesh,
)

# seq lengths divisible by sp=4; heads divisible by tp is not exercised
# here (tp=1) — the 4-axis composition is covered by tests/_mesh32_child.py
SRC, TGT = 32, 8


def _cfg(impl):
    return t5_mod.T5Config(
        vocab_size=96, d_model=32, d_kv=8, d_ff=64, num_layers=2,
        num_decoder_layers=2, num_heads=4, relative_attention_num_buckets=8,
        relative_attention_max_distance=20, dropout_rate=0.0,
        attention_impl=impl)


def _batch(cfg, batch=4, seed=0):
    r = np.random.RandomState(seed)
    src_ids = r.randint(2, cfg.vocab_size, (batch, SRC)).astype(np.int32)
    src_mask = np.ones((batch, SRC), np.int32)
    src_mask[1, 20:] = 0
    src_ids[1, 20:] = cfg.pad_token_id
    tgt_ids = r.randint(2, cfg.vocab_size, (batch, TGT)).astype(np.int32)
    return jnp.asarray(src_ids), jnp.asarray(src_mask), jnp.asarray(tgt_ids)


def _loss_and_grads(impl, mesh):
    cfg = _cfg(impl)
    model = t5_mod.T5ForConditionalGeneration(cfg)
    params = auto_models.init_params(model, cfg, seed=0)
    src_ids, src_mask, tgt_ids = _batch(cfg)

    def loss_fn(p):
        logits = model.apply({"params": p}, src_ids, src_mask, tgt_ids,
                             deterministic=True)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32))
        tgt = jax.nn.one_hot(tgt_ids, cfg.vocab_size)
        return -jnp.mean(jnp.sum(logp * tgt, axis=-1))

    with use_mesh(mesh):
        loss, grads = jax.jit(jax.value_and_grad(loss_fn))(params)
        return (float(jax.device_get(loss)),
                jax.device_get(jax.tree.map(np.asarray, grads)))


def test_t5_ring_encoder_matches_xla(devices8):
    mesh = build_mesh(MeshConfig(dp=2, sp=4), devices=devices8)
    loss_x, grads_x = _loss_and_grads("xla", mesh)
    loss_r, grads_r = _loss_and_grads("ring", mesh)
    assert np.isfinite(loss_r)
    np.testing.assert_allclose(loss_r, loss_x, atol=1e-5)
    flat_x = jax.tree.leaves(grads_x)
    flat_r = jax.tree.leaves(grads_r)
    assert len(flat_x) == len(flat_r)
    for gx, gr in zip(flat_x, flat_r):
        np.testing.assert_allclose(gr, gx, atol=2e-5)


def test_t5_ring_param_tree_matches_xla():
    # the ring-mode bias table must create the SAME parameter path/shape
    # (self_attn/rel_bias/embedding) so checkpoints swap between modes
    t_x = auto_models.init_params(
        t5_mod.T5ForConditionalGeneration(_cfg("xla")), _cfg("xla"), seed=0)
    t_r = auto_models.init_params(
        t5_mod.T5ForConditionalGeneration(_cfg("ring")), _cfg("ring"), seed=0)
    paths_x = {jax.tree_util.keystr(p): v.shape
               for p, v in jax.tree_util.tree_flatten_with_path(t_x)[0]}
    paths_r = {jax.tree_util.keystr(p): v.shape
               for p, v in jax.tree_util.tree_flatten_with_path(t_r)[0]}
    assert paths_x == paths_r


def test_t5_ring_generate_matches_xla(devices8):
    # decode path (KV cache) materializes bias from the table — greedy
    # generation must be identical between modes
    from huggingface_sagemaker_tensorflow_distributed_tpu.models import generate as gen

    outs = {}
    mesh = build_mesh(MeshConfig(dp=2, sp=4), devices=devices8)
    for impl in ("xla", "ring"):
        cfg = _cfg(impl)
        model = t5_mod.T5ForConditionalGeneration(cfg)
        params = auto_models.init_params(model, cfg, seed=0)
        src_ids, src_mask, _ = _batch(cfg)
        with use_mesh(mesh):
            outs[impl] = np.asarray(gen.generate(
                model, params, src_ids, src_mask, max_new_tokens=6))
    np.testing.assert_array_equal(outs["ring"], outs["xla"])
