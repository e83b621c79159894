"""Xing4 (``model_type`` ``xing4_0``): the DeepSeek-V2 layout (latent
attention, shared and routed SwiGLU experts: ``models/deepseek_v2.py``)
with two mechanisms of its own.

- **The residual path is manifold-constrained hyper-connections** (mHC,
  arXiv:2512.24880, after hyper-connections, arXiv:2409.19606). A token's
  hidden state is ``n = hc_mult`` streams ``x [n, C]`` (the embedding
  copied into each; summed behind the last layer, before the final norm).
  Every sub-layer ``F`` (attention with its input norm, the FFN with its
  norm: two a layer) is wrapped (:class:`HyperConnection`), all of the
  maps in float32::

      xbar   = RMSNorm_{nC}(vec(x))            one norm over the n C values, no weight
      hpre | hpost | hres = xbar Phi           Phi [n C, n + n + n^2]
      H_pre  = sigmoid(a_pre hpre + b_pre)                      [n]
      H_post = 2 sigmoid(a_post hpost + b_post)                 [n]
      M      = exp(clip(a_res mat(hres) + b_res, lo, hi))       [n, n]
      H_res  = Sinkhorn-Knopp(M): hc_sinkhorn_iters times, every column
               divided by its sum + hc_eps, then every row by its
      u = H_pre x        y = F(u)        x+ = H_res x + H_post^T y

  ``H_res`` is then (nearly) doubly stochastic: a layer can mix the
  streams but neither grow nor shrink their sum. The wrap is a function
  of ONE token's streams, so the decode cache is the latent attention's
  and nothing else: the serving engine sees a ``latent`` kind and no
  stream. Each wrap sows the largest ``|row or column sum - 1|`` of the
  ``H_res`` it made into :data:`MOE_STATS` (``mhc_defect``): whether the
  iterations converged on the tokens that were run.

- **The gate is sigmoid, bias-corrected and renormalised**
  (``models/moe.py::sigmoid_bias_gate``: ``scoring_func`` sigmoid,
  ``topk_method`` noaux_tc with one group, ``norm_topk_prob``): a token's
  experts are the top ``num_experts_per_tok`` of ``sigmoid(u W_r) +
  e_score_correction_bias``, weighted by their sigmoids renormalised to
  sum to ``routed_scaling_factor``. The experts themselves are
  ``dropless_experts``.

Reused as they are: ``DeepseekV2Attention`` (with ``cached_latent``,
``latent_path``, ``expanded_form`` and both fused latent kernels),
``LlamaMlp``, ``LlamaRMSNorm``, the backbone's embedding and rotary
tables (``embed_and_rope``).

Out of scope (ROADMAP): the next-token-prediction module
(``num_nextn_predict_layers``: the main model's logits do not depend on
it) and self-drafting from it, training (the backward through Sinkhorn,
recomputation of ``n`` streams), a pipeline hand-off that carries ``n``
streams, the streams under a tensor-parallel mesh, a published
checkpoint (``models/convert.py`` has no mapping for this family).
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from huggingface_sagemaker_tensorflow_distributed_tpu.models.deepseek_v2 import (
    EXPANDED_FORMS,
    MOE_STATS,
    DeepseekV2Attention,
    DeepseekV2Config,
    DeepseekV2MoE,
    _seen_form,
    embed_and_rope,
    latent_moe_config_kw,
    latent_path,
    refuse_unless,
)
from huggingface_sagemaker_tensorflow_distributed_tpu.models.llama import (
    LlamaMlp,
    LlamaRMSNorm,
)
from huggingface_sagemaker_tensorflow_distributed_tpu.models.moe import (
    sigmoid_bias_gate,
)

GATE = "sigmoid_bias"


@dataclass(frozen=True)
class Xing4Config(DeepseekV2Config):
    """``DeepseekV2Config`` (attention and the experts read it as it is)
    with the residual path's sizes; the gate is the family's one."""

    model_type: str = "xing4_0"
    n_group: int = 1
    topk_group: int = 1
    hc_mult: int = 4                       # residual streams a token
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    mhc_h_res_clamp_min: float = -30.0
    mhc_h_res_clamp_max: float = 30.0

    def __post_init__(self):
        super().__post_init__()
        if self.hc_mult < 2:
            raise ValueError(
                f"hc_mult {self.hc_mult} is not implemented: the residual "
                "path here is hyper-connections over at least 2 streams "
                "(one stream is the plain residual of deepseek_v2)")
        if self.n_group != 1 or self.topk_group != 1:
            raise ValueError(
                f"n_group {self.n_group} / topk_group {self.topk_group} is "
                "not implemented for xing4_0: its gate chooses among all "
                "experts as one group")


def xing4_config_from_hf(hf_config: dict, **overrides) -> Xing4Config:
    """The program's configuration from a published ``config.json``
    mapping. Refuses by name what this code does not run, rather than
    load and diverge."""
    refuse_unless("xing4_0", hf_config, {
        "scoring_func": ("sigmoid", "sigmoid"),
        "topk_method": ("noaux_tc", "noaux_tc"),
        "norm_topk_prob": (True, True),
        "n_group": (1, 1),
        "topk_group": (1, 1),
        "moe_layer_freq": (1, 1),
        "attention_bias": (False, False),
    }, "this family's gate is sigmoid scores, selection by score plus "
       "bias over ONE group, renormalised weights "
       "(models/moe.py::sigmoid_bias_gate); a softmax, group-limited "
       "gate is the deepseek_v2 family's")
    kw = latent_moe_config_kw(hf_config, "xing4_0")
    kw.update(
        hc_mult=hf_config["hc_mult"],
        hc_sinkhorn_iters=hf_config.get("hc_sinkhorn_iters", 20),
        hc_eps=hf_config.get("hc_eps", 1e-6),
        mhc_h_res_clamp_min=float(hf_config.get("mhc_h_res_clamp_min", -30)),
        mhc_h_res_clamp_max=float(hf_config.get("mhc_h_res_clamp_max", 30)),
        # only eos is read (a request ends on it): the last id unless given
        bos_token_id=hf_config.get("bos_token_id", 0),
        eos_token_id=hf_config.get("eos_token_id",
                                   hf_config["vocab_size"] - 1),
    )
    if hf_config.get("pad_token_id") is None:
        kw["pad_token_id"] = kw["eos_token_id"]
    kw.update(overrides)
    kw.pop("use_pooler", None)             # encoder-family knob
    return Xing4Config(**kw)


def sinkhorn(m, iters: int, eps: float):
    """Sinkhorn-Knopp on positive ``m`` [..., n, n]: ``iters`` times,
    every column divided by its sum + ``eps``, then every row by its."""
    def step(_, m):
        m = m / (m.sum(axis=-2, keepdims=True) + eps)
        return m / (m.sum(axis=-1, keepdims=True) + eps)

    return lax.fori_loop(0, iters, step, m, unroll=True)


def mhc_maps(x, phi, alpha, b_pre, b_post, b_res, *, iters: int, eps: float,
             clamp: tuple):
    """``(H_pre [..., n], H_post [..., n], H_res [..., n, n])`` of the
    streams ``x`` [..., n, C], all float32 (the module's text has the
    equations). ``phi`` [n C, n + n + n^2], ``alpha`` [3] (pre, post,
    res), ``b_pre``/``b_post`` [n], ``b_res`` [n, n]. The one matmul runs
    at ``HIGHEST``: ``H_res`` is an exponential of its output, and a
    float32 matmul in bf16 passes would move it in its second digit."""
    n = x.shape[-2]
    flat = x.astype(jnp.float32).reshape(*x.shape[:-2], -1)
    xbar = flat * lax.rsqrt(jnp.mean(flat * flat, axis=-1, keepdims=True)
                            + eps)
    h = jnp.einsum("...k,ko->...o", xbar, phi.astype(jnp.float32),
                   precision=lax.Precision.HIGHEST)
    h_pre = jax.nn.sigmoid(alpha[0] * h[..., :n] + b_pre)
    h_post = 2.0 * jax.nn.sigmoid(alpha[1] * h[..., n:2 * n] + b_post)
    raw = alpha[2] * h[..., 2 * n:].reshape(*h.shape[:-1], n, n) + b_res
    h_res = sinkhorn(jnp.exp(jnp.clip(raw, *clamp)), iters, eps)
    return h_pre, h_post, h_res


def mhc_defect(h_res, token_mask=None):
    """The largest ``|row or column sum - 1|`` over the ``H_res`` [..., n,
    n] of the tokens ``token_mask`` [...] marks real (all without one)."""
    d = jnp.maximum(jnp.abs(h_res.sum(axis=-1) - 1.0).max(axis=-1),
                    jnp.abs(h_res.sum(axis=-2) - 1.0).max(axis=-1))
    if token_mask is not None:
        d = jnp.where(token_mask, d, 0.0)
    return d.max()


def _b_res_init(on: float):
    # near the identity: exp(on) on the diagonal against exp(0) off it
    return lambda key, shape, dtype=jnp.float32: on * jnp.eye(
        shape[0], dtype=dtype)


class HyperConnection(nn.Module):
    """One sub-layer's wrap over the streams ``x`` [B, S, n, C], in two
    halves around the sub-layer ``F``: this module makes the maps and the
    sub-layer's input, ``(u, maps) = hc(x)``, and :func:`mhc_merge` makes
    ``x+ = H_res x + H_post^T y`` of ``y = F(u)``. The maps are float32;
    the streams stay in the compute dtype and both mixes accumulate in
    float32 (four terms a value)."""

    config: Xing4Config

    @nn.compact
    def __call__(self, x, token_mask=None):
        cfg = self.config
        n, C = x.shape[-2:]
        phi = self.param("phi", nn.initializers.normal(cfg.initializer_range),
                         (n * C, 2 * n + n * n), cfg.param_dtype)
        # 3 + 2 n + n^2 values: float32 whatever the weights are
        alpha = self.param("alpha", nn.initializers.constant(0.01), (3,),
                           jnp.float32)
        b_pre = self.param("b_pre", nn.initializers.constant(
            -math.log(n - 1.0)), (n,), jnp.float32)    # sigmoid -> 1 / n
        b_post = self.param("b_post", nn.initializers.zeros, (n,),
                            jnp.float32)
        b_res = self.param("b_res", _b_res_init(8.0), (n, n), jnp.float32)
        h_pre, h_post, h_res = mhc_maps(
            x, phi, alpha, b_pre, b_post, b_res,
            iters=cfg.hc_sinkhorn_iters, eps=cfg.hc_eps,
            clamp=(cfg.mhc_h_res_clamp_min, cfg.mhc_h_res_clamp_max))
        self.sow(MOE_STATS, "mhc_defect", mhc_defect(h_res, token_mask))
        u = jnp.sum(h_pre[..., None] * x.astype(jnp.float32), axis=-2)
        return u.astype(cfg.dtype), (h_post, h_res)


def mhc_merge(x, maps, y):
    """``H_res x + H_post^T y``: the streams ``x`` [B, S, n, C] mixed and
    the sub-layer's output ``y`` [B, S, C] written into each, in ``x``'s
    dtype."""
    h_post, h_res = maps
    mixed = jnp.sum(h_res[..., None]
                    * x.astype(jnp.float32)[..., None, :, :], axis=-2)
    return (mixed + h_post[..., None]
            * y.astype(jnp.float32)[..., None, :]).astype(x.dtype)


class Xing4MoE(DeepseekV2MoE):
    """``DeepseekV2MoE`` under this family's gate; the selection bias is
    a parameter of the layer (float32; zeros at init)."""

    def gate(self, logits):
        cfg = self.config
        bias = self.param("e_score_correction_bias", nn.initializers.zeros,
                          (cfg.n_routed_experts,), jnp.float32)
        return sigmoid_bias_gate(jax.nn.sigmoid(logits), bias,
                                 cfg.num_experts_per_tok,
                                 cfg.routed_scaling_factor)


class Xing4Block(nn.Module):
    config: Xing4Config
    layer_index: int = 0

    @nn.compact
    def __call__(self, x, key_valid=None, rope=None, decode: bool = False,
                 token_mask=None):
        cfg = self.config
        u, maps = HyperConnection(cfg, name="attn_hc")(x, token_mask)
        x = mhc_merge(x, maps, DeepseekV2Attention(cfg, name="self_attn")(
            LlamaRMSNorm(cfg, name="input_ln")(u), key_valid, rope, decode))
        u, maps = HyperConnection(cfg, name="ffn_hc")(x, token_mask)
        normed = LlamaRMSNorm(cfg, name="post_attn_ln")(u)
        if self.layer_index < cfg.first_k_dense_replace:
            return mhc_merge(x, maps, LlamaMlp(cfg, name="mlp")(normed))
        return mhc_merge(x, maps,
                         Xing4MoE(cfg, name="moe")(normed, token_mask))


class Xing4Model(nn.Module):
    config: Xing4Config

    @nn.compact
    def __call__(self, input_ids, attention_mask=None, position_ids=None,
                 decode: bool = False, token_mask=None):
        cfg = self.config
        h, key_valid, rope = embed_and_rope(self, input_ids, attention_mask,
                                            position_ids, decode)
        # the embedding copied into the n streams
        x = jnp.broadcast_to(h[:, :, None, :], (
            *h.shape[:2], cfg.hc_mult, cfg.hidden_size))
        for i in range(cfg.num_layers):
            x = Xing4Block(cfg, layer_index=i, name=f"layers_{i}")(
                x, key_valid, rope, decode, token_mask)
        # the streams summed
        h = jnp.sum(x.astype(jnp.float32), axis=-2).astype(cfg.dtype)
        return LlamaRMSNorm(cfg, name="final_ln")(h)


class Xing4ForCausalLM(nn.Module):
    """Same call signature as ``DeepseekV2ForCausalLM`` (so
    ``generate_causal`` and the serving engine drive it unchanged), plus
    ``logit_positions`` ``[B]``: the one position a row whose logits are
    wanted (the result is then ``[B, 1, V]``: four rows of 512 float32
    logits over 131,072 tokens would be 1.07 GB the chip does not have
    beside this model's weights)."""

    config: Xing4Config

    latent_path = staticmethod(latent_path)
    EXPANDED_FORMS = EXPANDED_FORMS
    takes_logit_positions = True

    def expanded_form(self, q_len: int, width: int) -> str:
        return _seen_form(self.config, q_len, width)

    def residual_kw(self) -> dict:
        """What the serving engine writes beside ``latent_path``: the
        residual streams a token has and the gate that routes it."""
        return {"residual_streams": self.config.hc_mult, "gate": GATE}

    def setup(self):
        cfg = self.config
        self.backbone = Xing4Model(cfg)
        self.lm_head = nn.Dense(
            cfg.vocab_size, use_bias=False, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            kernel_init=nn.initializers.normal(cfg.initializer_range),
            name="lm_head")

    def __call__(self, input_ids, attention_mask=None, token_type_ids=None,
                 position_ids=None, deterministic: bool = True,
                 decode: bool = False, token_mask=None,
                 logit_positions=None):
        hidden = self.backbone(input_ids, attention_mask, position_ids,
                               decode, token_mask)
        if logit_positions is not None:
            hidden = jnp.take_along_axis(
                hidden, logit_positions[:, None, None], axis=1)
        return self.lm_head(hidden).astype(jnp.float32)
