"""Int8 weight-only quantization for generation.

Beyond-parity capability. Autoregressive decode on TPU is HBM-bandwidth
-bound: every generated token re-reads the full weight set, so halving
(bf16) or quartering (fp32) the bytes behind each matmul raises decode
throughput roughly in proportion — compute stays in the model dtype and
the MXU never sees int8. Symmetric per-output-channel scales keep the
scheme zero-point-free, which is what XLA fuses cleanly: the dequant
(``int8 -> dtype multiply``) is a producer elementwise op folded into
the matmul's operand read, so the bf16 weight tensor never round-trips
through HBM.

The reference has no quantization story at all (its serving path is
``save_pretrained`` and whatever the downstream endpoint does,
reference ``scripts/train.py:182-183``); this is in-repo and targeted
at decoding, where the weights' bytes bound the step.

Scope: the dense kernels of the generating families — GPT-2
(qkv / attn_out / fc_in / fc_out), T5 (query/key/value/attention_out,
wi / wi_0 / wi_1 / wo) and BART/mBART (q/k/v/o, fc1/fc2); each family's
``_dense`` helper is its single chokepoint. Embeddings and LM heads
(tied or not) stay full precision: embedding tables are lookups (no
bandwidth win) and the output projection is where quantization error
lands directly on the logits.
"""

from __future__ import annotations

import re
from typing import Any

import jax.numpy as jnp
import numpy as np
from flax import linen as nn
from flax.traverse_util import flatten_dict, unflatten_dict

# per-family dense-kernel leaves that become int8 (path regex against
# the "/"-joined param path ending in "/kernel"); LM heads excluded
GPT2_QUANT_TARGETS = r"(qkv|attn_out|fc_in|fc_out)/kernel$"
T5_QUANT_TARGETS = r"(query|key|value|attention_out|wi|wi_0|wi_1|wo)/kernel$"
BART_QUANT_TARGETS = r"(query|key|value|attention_out|fc1|fc2)/kernel$"
LLAMA_QUANT_TARGETS = (
    r"(q_proj|k_proj|v_proj|o_proj|gate_proj|up_proj|down_proj)/kernel$")


class Int8Dense(nn.Module):
    """Drop-in for ``nn.Dense`` holding an int8 kernel + per-output
    -channel fp32 scales. Params come from :func:`quantize_params`
    (init gives zeros/ones placeholders — a quantized model is loaded,
    never trained; training stays full precision)."""

    features: int
    dtype: Any = jnp.float32
    use_bias: bool = True                 # False for T5's bias-free denses

    @nn.compact
    def __call__(self, x):
        in_features = x.shape[-1]
        q = self.param("kernel_q", nn.initializers.zeros,
                       (in_features, self.features), jnp.int8)
        scale = self.param("kernel_scale", nn.initializers.ones,
                           (self.features,), jnp.float32)
        # dequant is elementwise on the weight: XLA fuses it into the
        # dot's operand read; only int8 bytes cross HBM
        w = q.astype(self.dtype) * scale.astype(self.dtype)[None, :]
        y = x @ w
        if self.use_bias:
            bias = self.param("bias", nn.initializers.zeros,
                              (self.features,), jnp.float32)
            y = y + bias.astype(self.dtype)
        return y


def make_dense(cfg, features: int, kernel_init, *, use_bias: bool = True,
               name: str | None = None) -> nn.Module:
    """THE dense-construction chokepoint for the generating families:
    fp (``nn.Dense``) or int8 (:class:`Int8Dense`) by ``cfg.weight_quant``
    — so a new weight_quant mode lands here once, not per family."""
    if getattr(cfg, "weight_quant", "none") == "int8":
        return Int8Dense(features, dtype=cfg.dtype, use_bias=use_bias,
                         name=name)
    return nn.Dense(features, use_bias=use_bias, dtype=cfg.dtype,
                    param_dtype=cfg.param_dtype, kernel_init=kernel_init,
                    name=name)


def quantize_kernel(w: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Symmetric per-output-channel int8: scale = max|w|/127 per column,
    q = round(w/scale). Returns (q int8 [in, out], scale fp32 [out])."""
    w = np.asarray(w, np.float32)
    amax = np.max(np.abs(w), axis=0)
    scale = np.where(amax > 0, amax / 127.0, 1.0).astype(np.float32)
    q = np.clip(np.round(w / scale[None, :]), -127, 127).astype(np.int8)
    return q, scale


def quantize_params(params: Any, targets: str) -> tuple[Any, dict]:
    """Rewrite targeted ``.../kernel`` leaves into ``kernel_q`` +
    ``kernel_scale`` (the :class:`Int8Dense` layout); everything else
    passes through. Returns (quantized tree, stats dict)."""
    rx = re.compile(targets)
    flat = flatten_dict(params)
    out: dict = {}
    n_quant = bytes_before = bytes_after = 0
    for path, leaf in flat.items():
        path_s = "/".join(str(p) for p in path)
        if rx.search(path_s) and getattr(leaf, "ndim", 0) == 2:
            q, scale = quantize_kernel(np.asarray(leaf))
            out[path[:-1] + ("kernel_q",)] = jnp.asarray(q)
            out[path[:-1] + ("kernel_scale",)] = jnp.asarray(scale)
            n_quant += 1
            bytes_before += leaf.size * np.dtype(
                np.asarray(leaf).dtype).itemsize
            bytes_after += q.size + scale.size * 4
        else:
            out[path] = leaf
    if n_quant == 0:
        raise ValueError(f"quant target {targets!r} matched no kernels")
    stats = {"kernels_quantized": n_quant, "bytes_before": bytes_before,
             "bytes_after": bytes_after}
    return unflatten_dict(out), stats


def quantize_for_generation(model, params) -> tuple[Any, Any, dict]:
    """(model, params) -> (int8 model, int8 params, stats) for
    generation. The returned model is the same architecture with
    ``weight_quant='int8'`` (the family's ``_dense`` helper swaps in
    :class:`Int8Dense`); KV cache, decode schedules and sampling are
    untouched. Covers GPT-2, Llama, T5 and BART/mBART."""
    import dataclasses

    from huggingface_sagemaker_tensorflow_distributed_tpu.models.bart import (
        BartConfig,
    )
    from huggingface_sagemaker_tensorflow_distributed_tpu.models.gpt2 import (
        Gpt2Config,
    )
    from huggingface_sagemaker_tensorflow_distributed_tpu.models.llama import (
        LlamaConfig,
    )
    from huggingface_sagemaker_tensorflow_distributed_tpu.models.t5 import (
        T5Config,
    )

    cfg = model.config
    targets = {Gpt2Config: GPT2_QUANT_TARGETS, T5Config: T5_QUANT_TARGETS,
               BartConfig: BART_QUANT_TARGETS,
               LlamaConfig: LLAMA_QUANT_TARGETS}.get(type(cfg))
    if targets is None:
        raise ValueError(
            "int8 weight-only quantization covers the generating "
            "families (GPT-2, Llama, T5, BART/mBART); got "
            f"{type(cfg).__name__}")
    qcfg = dataclasses.replace(cfg, weight_quant="int8")
    qmodel = type(model)(qcfg)
    qparams, stats = quantize_params(params, targets)
    return qmodel, qparams, stats


# original (GPT-2-only) entry point; kept as an alias
quantize_gpt2 = quantize_for_generation
