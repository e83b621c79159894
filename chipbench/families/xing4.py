"""Xing4 through the program's own loader
(``models/xing4.py::xing4_config_from_hf``).

As the DeepSeek-V2 file does, the configuration FILE counts the routed
experts held here under the published key (``n_routed_experts``) and
states the deployment beside it: ``expert_parallel`` shares, of which
this is ``expert_rank`` (this configuration holds every expert: 1 and
0). The program's loader takes the router's published width and the
share as ``experts_held`` / ``expert_rank``."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from huggingface_sagemaker_tensorflow_distributed_tpu.models.xing4 import (
    Xing4ForCausalLM,
    xing4_config_from_hf,
)


def program_config(config: dict) -> dict:
    """The mapping the program's loader reads, from the file's."""
    held = int(config["n_routed_experts"])
    return dict(config, n_routed_experts=held * int(config["expert_parallel"]),
                experts_held=held, expert_rank=int(config["expert_rank"]))


def build(config: dict, seed: int, *, dtype: str):
    """(model, params): parameters made on the device by ONE jitted init
    from the seed (the file's ``weights_seed`` where it has one), in the
    type they are served in; the wrap's ``alpha`` and biases and the
    gate's selection bias stay float32."""
    dt = jnp.dtype(dtype)
    cfg = xing4_config_from_hf(program_config(config), dtype=dt,
                               param_dtype=dt)
    model = Xing4ForCausalLM(cfg)
    dummy = jnp.ones((1, 8), jnp.int32)
    params = jax.jit(lambda key: model.init(key, dummy, dummy)["params"])(
        jax.random.PRNGKey(int(config.get("weights_seed", seed))))
    return model, params
