"""Olmo-Hybrid through the program's own loader
(``models/olmo_hybrid.py::olmo_hybrid_config_from_hf``): the configuration
file's published keys as they stand (``layer_types`` is read as the
per-layer pattern; ``num_hidden_layers`` and ``layer_types`` are cut
together), plus the ids its ``assumed`` group states."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from huggingface_sagemaker_tensorflow_distributed_tpu.models.olmo_hybrid import (
    OlmoHybridForCausalLM,
    olmo_hybrid_config_from_hf,
)


def build(config: dict, seed: int, *, dtype: str):
    """(model, params): parameters made on the device by ONE jitted init
    from the seed, in the type they are served in (the decay's two
    parameters a layer, ``A_log`` and ``dt_bias``, stay float32)."""
    dt = jnp.dtype(dtype)
    cfg = olmo_hybrid_config_from_hf(config, dtype=dt, param_dtype=dt)
    model = OlmoHybridForCausalLM(cfg)
    dummy = jnp.ones((1, 8), jnp.int32)
    params = jax.jit(lambda key: model.init(key, dummy, dummy)["params"])(
        jax.random.PRNGKey(seed))
    return model, params
