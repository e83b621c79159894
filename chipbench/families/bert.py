"""BERT-family encoder for sequence classification, built through the
program's own classes from an HF-style configuration file."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from huggingface_sagemaker_tensorflow_distributed_tpu.models.bert import (
    BertForSequenceClassification,
)
from huggingface_sagemaker_tensorflow_distributed_tpu.models.layers import (
    EncoderConfig,
)

def build(config: dict, seed: int, *, attention_impl: str, dtype: str):
    """(model, params): parameters made on the device by ONE jitted
    init from the seed, float32 as the trainer keeps them."""
    cfg = EncoderConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        num_layers=config["num_hidden_layers"],
        num_heads=config["num_attention_heads"],
        intermediate_size=config["intermediate_size"],
        max_position_embeddings=config["max_position_embeddings"],
        type_vocab_size=config["type_vocab_size"],
        hidden_act=config["hidden_act"],
        layer_norm_eps=config["layer_norm_eps"],
        hidden_dropout=config["hidden_dropout_prob"],
        attention_dropout=config["attention_probs_dropout_prob"],
        pad_token_id=config["pad_token_id"],
        initializer_range=config["initializer_range"],
        dtype=jnp.dtype(dtype), param_dtype=jnp.float32,
        attention_impl=attention_impl)
    model = BertForSequenceClassification(
        cfg, num_labels=int(config.get("num_labels", 2)))
    dummy = jnp.ones((1, 8), jnp.int32)
    params = jax.jit(lambda key: model.init(key, dummy, dummy)["params"])(
        jax.random.PRNGKey(seed))
    return model, params
