"""DeepSeek-V2 through the program's own loader
(``models/deepseek_v2.py::deepseek_v2_config_from_hf``).

The configuration FILE counts the routed experts held here under the
published key (``n_routed_experts``, listed in its ``reduced``) and states
the deployment beside it: ``expert_parallel`` shares, of which this is
``expert_rank``. The program's loader takes the router's published width
and the share as ``experts_held`` / ``expert_rank``."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from huggingface_sagemaker_tensorflow_distributed_tpu.models.deepseek_v2 import (
    DeepseekV2ForCausalLM,
    deepseek_v2_config_from_hf,
)


def program_config(config: dict) -> dict:
    """The mapping the program's loader reads, from the file's."""
    held = int(config["n_routed_experts"])
    return dict(config, n_routed_experts=held * int(config["expert_parallel"]),
                experts_held=held, expert_rank=int(config["expert_rank"]))


def build(config: dict, seed: int, *, dtype: str):
    """(model, params): parameters made on the device by ONE jitted init,
    in the type they are served in; the held experts of a layer are
    stacked ``[held, ...]`` arrays. The init's key is the file's
    ``weights_seed`` where it has one, else ``seed``: routing follows the
    weights, and how many (token, expert) pairs land on the experts held
    here is a property of one realisation of the routers, so a cell whose
    work must be the same in every run holds the weights to one
    realisation as it holds its lengths (tokens still come from
    ``--seed``)."""
    dt = jnp.dtype(dtype)
    cfg = deepseek_v2_config_from_hf(program_config(config), dtype=dt,
                                     param_dtype=dt)
    model = DeepseekV2ForCausalLM(cfg)
    dummy = jnp.ones((1, 8), jnp.int32)
    params = jax.jit(lambda key: model.init(key, dummy, dummy)["params"])(
        jax.random.PRNGKey(int(config.get("weights_seed", seed))))
    return model, params
