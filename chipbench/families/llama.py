"""Llama-layout decoders (llama, mistral, qwen2) through the program's
own loader (``models/llama.py::llama_config_from_hf``)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from huggingface_sagemaker_tensorflow_distributed_tpu.models.llama import (
    LlamaForCausalLM,
    llama_config_from_hf,
)

def build(config: dict, seed: int, *, dtype: str):
    """(model, params): parameters made on the device by ONE jitted
    init from the seed, in the type they are served in."""
    dt = jnp.dtype(dtype)
    cfg = llama_config_from_hf(config, dtype=dt, param_dtype=dt)
    model = LlamaForCausalLM(cfg)
    dummy = jnp.ones((1, 8), jnp.int32)
    params = jax.jit(lambda key: model.init(key, dummy, dummy)["params"])(
        jax.random.PRNGKey(seed))
    return model, params
