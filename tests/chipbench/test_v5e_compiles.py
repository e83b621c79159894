"""Rehearsal compiles for the v5e, without the chip: the programs of the
cells at their real sizes, compiled by the TPU's compiler for a chip that
is described and not attached (``on-chip-measurement`` guide, section 2).
They settle the training batch and the serving deployment before any
chip time is spent, and guard every later PR. A compile that passes is
not a chip run. All in this one file; the topology is described inside a
fixture, never at import.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import spec

HBM_BYTES = 15.75 * 2 ** 30   # what the v5e's compiler allows a program


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever keeps libtpu away
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def _total_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)


def _config(name):
    return spec.load_json(os.path.join(spec.HERE, "configs", name + ".json"))


def _traffic(name):
    return spec.load_json(os.path.join(spec.HERE, "traffic", name + ".json"))


def test_bert_large_train_step_at_the_cells_batch(topo, monkeypatch):
    """The train step of ``bert-large-ft-s512`` (batch 16, 512 tokens,
    bf16, flash, no remat) compiles for one v5e chip with at least 10%
    of its memory free, and holds the three flash kernels."""
    from huggingface_sagemaker_tensorflow_distributed_tpu.config import (
        TrainConfig,
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
    from huggingface_sagemaker_tensorflow_distributed_tpu.parallel.mesh import (
        use_mesh,
    )
    from huggingface_sagemaker_tensorflow_distributed_tpu.parallel.sharding import (
        batch_column_sharding,
    )
    from huggingface_sagemaker_tensorflow_distributed_tpu.train import Trainer

    cfg, traffic = _config("bert-large-uncased-wwm"), _traffic("ft-s512")
    batch, seq = traffic["per_chip_batch"], traffic["seq_len"]
    devs = [topo.devices[0]]
    # the flash wrapper asks jax.devices() whether to interpret the
    # kernel: steer it here, in the test, to lower for the TPU
    monkeypatch.setattr(jax, "devices", lambda *a, **k: devs)
    tcfg = TrainConfig(dtype="bfloat16", train_batch_size=batch,
                       max_seq_length=seq, log_every_steps=0, seed=0)
    mcfg = EncoderConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        intermediate_size=cfg["intermediate_size"],
        max_position_embeddings=cfg["max_position_embeddings"],
        dtype=jnp.bfloat16, attention_impl="flash")
    model = BertForSequenceClassification(mcfg, num_labels=2)
    dummy = jnp.ones((1, 8), jnp.int32)
    pshape = jax.eval_shape(
        lambda k: model.init(k, dummy, dummy)["params"], jax.random.PRNGKey(0))
    mesh = build_mesh(MeshConfig(dp=-1), devices=devs)
    hold = {}

    def make(p):
        # the Trainer builds its optimizer state and shardings from the
        # parameters; under eval_shape nothing is placed on a device
        hold["trainer"] = Trainer(tcfg, model, p, mesh)
        return hold["trainer"].state

    sshape = jax.eval_shape(make, pshape)
    trainer = hold["trainer"]
    state = jax.tree_util.tree_map(
        lambda l, s: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=s),
        sshape, trainer.state_shardings)
    two = batch_column_sharding(mesh, 2, seq)
    cols = {k: jax.ShapeDtypeStruct((batch, seq), jnp.int32, sharding=two)
            for k in ("input_ids", "attention_mask", "token_type_ids")}
    cols["labels"] = jax.ShapeDtypeStruct(
        (batch,), jnp.int32, sharding=batch_column_sharding(mesh, 1, None))
    with use_mesh(mesh):
        compiled = jax.jit(
            trainer._train_step_impl,
            in_shardings=(trainer.state_shardings, None),
            out_shardings=(trainer.state_shardings, None),
            donate_argnums=(0,)).lower(state, cols).compile()
    total = _total_bytes(compiled)
    assert total <= 0.9 * HBM_BYTES, total
    assert total >= 0.25 * HBM_BYTES, "a batch this small leaves the chip empty"
    text = compiled.as_text()
    for kernel in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        assert kernel in text, kernel


@pytest.fixture(scope="module")
def qwen(one_chip):
    """Model, shapes and the engine's cache plan at the deployment the
    two serving cells share."""
    from chipbench.families.llama import LlamaForCausalLM, llama_config_from_hf
    from huggingface_sagemaker_tensorflow_distributed_tpu.serve import engine

    cfg = _config("qwen2.5-3b")
    dep = cfg["deployment"]
    model = LlamaForCausalLM(llama_config_from_hf(
        cfg, dtype=jnp.bfloat16, param_dtype=jnp.bfloat16))
    dummy = jnp.ones((1, 8), jnp.int32)
    pshape = jax.eval_shape(
        lambda k: model.init(k, dummy, dummy)["params"], jax.random.PRNGKey(0))
    plan, pool_shapes = engine.build_cache_plan(model, pshape,
                                                dep["max_model_len"])
    token_bytes = sum(h * d * np.dtype(t).itemsize for h, d, t in pool_shapes)
    blocks = 1 + dep["kv_pool_bytes"] // (dep["block_size"] * token_bytes)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(lambda l: sds(l.shape, l.dtype), pshape)
    pools = [sds((blocks, dep["block_size"], h, d), t)
             for h, d, t in pool_shapes]

    def rows(n):
        nb = dep["max_model_len"] // dep["block_size"]
        return (sds((n, nb), jnp.int32), sds((n,), jnp.int32))

    def sampling(n):
        return (sds((n,), jnp.float32), sds((n,), jnp.int32),
                sds((n,), jnp.float32), sds((n, 2), jnp.uint32),
                sds((n,), jnp.int32))

    return dict(engine=engine, model=model, dep=dep, plan=plan, sds=sds,
                params=params, pools=pools, rows=rows, sampling=sampling,
                token_bytes=token_bytes,
                param_bytes=sum(int(np.prod(l.shape)) * 2 for l in
                                jax.tree_util.tree_leaves(pshape)))


def test_qwen_sizes_are_the_published_ones(qwen):
    assert qwen["token_bytes"] == 36864          # 36 layers x 2 x 2 x 128 x 2 B
    assert qwen["param_bytes"] == pytest.approx(6.17e9, rel=0.01)


@pytest.mark.parametrize("bucket", [2048, 8192])
def test_qwen_decode_step_fits_at_both_buckets(qwen, bucket):
    q, n = qwen, qwen["dep"]["num_slots"]
    tables, ctx = q["rows"](n)
    step = jax.jit(
        lambda p, pools, *a: q["engine"]._decode_step(
            q["model"], p, pools, *a, q["plan"], bucket, False),
        donate_argnums=(1,))
    compiled = step.lower(q["params"], q["pools"], q["sds"]((n,), jnp.int32),
                          tables, ctx, q["sds"]((n,), jnp.bool_),
                          *q["sampling"](n)).compile()
    assert _total_bytes(compiled) <= 0.95 * HBM_BYTES


def test_qwen_prefill_chunk_fits(qwen):
    q, g, c = qwen, 4, qwen["dep"]["prefill_chunk"]   # prefill_batch 4
    tables, start = q["rows"](g)
    step = jax.jit(
        lambda p, pools, *a: q["engine"]._prefill_chunk(
            q["model"], p, pools, *a, q["plan"], False),
        donate_argnums=(1,))
    compiled = step.lower(q["params"], q["pools"], q["sds"]((g, c), jnp.int32),
                          tables, start, q["sds"]((g,), jnp.int32),
                          *q["sampling"](g)).compile()
    assert _total_bytes(compiled) <= 0.95 * HBM_BYTES
