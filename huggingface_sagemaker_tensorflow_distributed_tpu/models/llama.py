"""Llama-family decoder: RoPE + GQA + SwiGLU + RMSNorm
(+ the Mistral and Qwen2 variants of the same layout).

Beyond-parity model family: the reference fine-tunes the BERT-era HF
zoo (reference ``scripts/train.py:117``); this adds the modern
decoder-only lineage — the Llama/Llama-2/3 layout, Mistral (sliding
-window attention, banded mask from logical positions so padded
prompts window correctly), and Qwen2 (hardcoded q/k/v biases,
per-layer window policy via ``max_window_layers``) — with HF
checkpoint parity — and it composes with the
framework's existing machinery for free: the causal-lm task loss,
``generate_causal`` (prefill + KV cache), LoRA (bias-free ``*_proj``
kernels), int8 weight-only decode, fused vocab-CE
(``hidden_and_embedding``), and the Megatron sharding rules
(``q|k|v_proj`` column-, ``o_proj|down_proj`` row-parallel).

Architecture (HF parity):
- token embeddings only (positions live in RoPE), no dropout;
- pre-norm blocks: ``x + attn(rms(x))`` then ``x + mlp(rms(x))``;
- rotary position embeddings in HF's rotate-half layout, applied to
  q/k after head split;
- grouped-query attention: ``num_kv_heads <= num_heads`` k/v heads,
  cached PRE-repeat (the GQA memory win), repeated to full heads for
  the attention kernel (Pallas flash on TPU);
- SwiGLU MLP ``down(silu(gate(x)) * up(x))``, all projections bias-free;
- RMSNorm (fp32 statistics island) with HF's epsilon placement;
- untied ``lm_head`` by default (``tie_word_embeddings`` supported —
  TinyLlama/Gemma-style).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from huggingface_sagemaker_tensorflow_distributed_tpu.models.layers import (
    ACT2FN,
    remat_policy,
)
from huggingface_sagemaker_tensorflow_distributed_tpu.ops.attention import (
    dot_product_attention,
    make_attention_mask,
)

NEG_INF = -1e9


@dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    num_layers: int = 32                   # num_hidden_layers
    num_heads: int = 32                    # num_attention_heads
    num_kv_heads: int = 32                 # num_key_value_heads (GQA)
    intermediate_size: int = 11008
    max_position_embeddings: int = 2048
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-5
    hidden_act: str = "silu"
    initializer_range: float = 0.02
    tie_word_embeddings: bool = False
    bos_token_id: int = 1
    eos_token_id: int = 2
    pad_token_id: int = 0
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32
    attention_impl: str = "xla"
    remat: bool = False
    remat_policy: str = "full"             # full | dots | dots_no_batch
    # int8 weight-only dense kernels for generation (models/quant.py)
    weight_quant: str = "none"             # none | int8
    # Mistral: attend only to the last N key positions (None = full
    # causal). On the default-positions training path the window runs
    # through the attention kernel (banded flash with tile-skipping on
    # TPU); custom position_ids and ring attention use a general
    # [B,1,S,S] banded mask instead.
    sliding_window: Optional[int] = None
    # first layer the window applies to (HF Qwen2 ``max_window_layers``
    # semantics: layers below it use full attention; 0 = window all)
    sliding_window_start_layer: int = 0
    # Qwen2: biases on q/k/v projections only (o/mlp stay bias-free)
    qkv_bias: bool = False
    # Gemma: q/k/v head size independent of hidden_size/num_heads
    # (None = hidden_size // num_heads, the Llama/Mistral/Qwen2 case)
    head_dim: Optional[int] = None
    # Gemma RMSNorm: scale applied as (1 + weight) in fp32 BEFORE the
    # cast back to the compute dtype (HF GemmaRMSNorm order)
    rms_unit_offset: bool = False
    # Gemma: embeddings multiplied by sqrt(hidden_size)
    embed_scale: bool = False
    # Llama-3.1+ long-context RoPE frequency scaling. Stored as a sorted
    # item tuple (NOT the HF dict) so the frozen config stays hashable;
    # ``rope_scaling_dict`` rebuilds the mapping. Supported rope_types:
    # "llama3" (NTK-by-parts smoothing) and "linear" (inv_freq/factor).
    rope_scaling: Optional[tuple] = None
    # Decode KV cache storage: "fp" keeps K/V in the param dtype; "int8"
    # stores symmetric per-(head, slot) int8 with an fp32 scale — long
    # -context decode is HBM-bound on the KV cache, so int8 halves the
    # cache bytes read per step vs bf16 (dequant fuses into the read).
    # Q/K/V math still runs in the compute dtype after dequant.
    kv_cache_dtype: str = "fp"             # fp | int8
    # GPipe pipeline parallelism over the block stack (models/pipeline.py;
    # training/scoring path — generation reloads dense)
    pipeline_stages: int = 0
    pipeline_microbatches: int = 0         # 0 → = pipeline_stages
    # Mixtral: every ``moe_every``-th block's MLP becomes a token-routed
    # SwiGLU expert bank (models/moe.py::MixtralMoeBlock) sharded over
    # the ``expert`` mesh axis. HF Mixtral is MoE at EVERY layer
    # (moe_every=1, the default here); Switch-style sparse placement is
    # moe_every=2. Router/capacity semantics match the encoder MoE.
    num_experts: int = 0                   # num_local_experts
    expert_top_k: int = 2                  # num_experts_per_tok
    moe_every: int = 1
    expert_capacity_factor: float = 1.25
    router_aux_coef: float = 0.02          # router_aux_loss_coef
    # The Olmo 2 / Olmo 3 block (models/olmo_hybrid.py runs its full
    # layers through this file). Each default leaves every other
    # configuration's parameter tree and traced program as they were.
    # RMSNorm over the WHOLE query and the whole key projection, before
    # the heads are split (params ``q_norm`` / ``k_norm``)
    qk_norm: bool = False
    # False: no rotary embedding at all (position comes from elsewhere)
    use_rope: bool = True
    # True: ``h = x + Norm(Attn(x))``, ``out = h + Norm(MLP(h))`` (a norm
    # after each sub-layer, before the residual add; params
    # ``post_attn_ln`` / ``post_mlp_ln``) instead of the pre-norm block
    post_norm: bool = False

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or self.hidden_size // self.num_heads

    @property
    def rope_scaling_dict(self) -> Optional[dict]:
        return dict(self.rope_scaling) if self.rope_scaling else None
    # which HF model_type this config round-trips as (llama | mistral |
    # qwen2 — same state-dict layout, different config.json)
    model_type: str = "llama"

    def __post_init__(self):
        if self.kv_cache_dtype not in ("fp", "int8"):
            raise ValueError(
                f"unknown kv_cache_dtype {self.kv_cache_dtype!r} "
                "(fp | int8)")
        if (self.post_norm or self.qk_norm or not self.use_rope) and (
                self.num_experts or self.pipeline_stages):
            raise ValueError(
                "post_norm / qk_norm / use_rope=False are the dense, "
                "unpipelined block's options: routed experts and "
                "pipeline_stages run blocks of their own that do not "
                "read them")
        if self.num_experts and self.model_type != "mixtral":
            # The only HF layout that can carry the expert bank is
            # Mixtral's: with any other model_type, save_pretrained
            # would write block_sparse_moe.* weights next to a
            # config.json that rebuilds a DENSE model, and the trained
            # experts would silently vanish on reload. Coerce the
            # layout-compatible variants (Mixtral IS Mistral attention +
            # experts); reject the ones whose knobs Mixtral's layout
            # cannot express. Enforced HERE so directly-constructed
            # configs get the same round-trip safety as from_pretrained.
            if self.model_type in ("llama", "mistral"):
                object.__setattr__(self, "model_type", "mixtral")
            else:
                raise ValueError(
                    f"num_experts > 0 is not supported for model_type "
                    f"{self.model_type!r}: the MoE export layout is HF "
                    "Mixtral's, which cannot express qkv biases / Gemma "
                    "norm semantics — upcycle a llama or mistral "
                    "checkpoint")


def llama_config_from_hf(hf_config: dict, **overrides) -> LlamaConfig:
    # silently-wrong-logits guards (repo convention: raise on unsupported
    # layouts rather than load-and-diverge, cf. the DeBERTa legacy-head
    # check in models/auto.py)
    scaling = hf_config.get("rope_scaling")
    rope_scaling = None
    if scaling:
        rope_type = scaling.get("rope_type", scaling.get("type"))
        if rope_type == "default":
            pass
        elif rope_type in ("linear", "llama3"):
            required = (("factor",) if rope_type == "linear" else
                        ("factor", "low_freq_factor", "high_freq_factor",
                         "original_max_position_embeddings"))
            missing = [k for k in required if k not in scaling]
            if missing:
                # fail at load time with names, not as a KeyError mid-jit
                raise ValueError(
                    f"rope_scaling type {rope_type!r} is missing required "
                    f"keys {missing}: {scaling!r}")
            rope_scaling = tuple(sorted(scaling.items()))
        else:
            # yarn/dynamic-NTK etc.: loading would silently use wrong
            # RoPE frequencies and diverge from HF
            raise ValueError(
                f"rope_scaling type {rope_type!r} is not implemented "
                "(supported: default, linear, llama3 — the Llama-3.1+ "
                f"long-context scaling): {scaling!r}")
    mt = hf_config.get("model_type", "llama")
    window_start = 0
    extra = {}
    if mt == "gemma":
        extra = dict(
            rms_unit_offset=True,
            embed_scale=True,
        )
    if mt == "qwen2":
        # Qwen2's modeling class hardcodes q/k/v biases (not a config
        # field); the o/mlp projections stay bias-free. Its window is
        # PER-LAYER: layers >= max_window_layers slide, earlier ones use
        # full attention (HF layer_types derivation).
        qkv_bias = True
        if hf_config.get("use_sliding_window"):
            window = hf_config.get("sliding_window")
            window_start = hf_config.get("max_window_layers", 28)
        else:
            window = None
    else:
        qkv_bias = False
        # Mixtral is a Mistral derivative: same optional sliding window
        window = (hf_config.get("sliding_window")
                  if mt in ("mistral", "mixtral") else None)
    if mt == "mixtral":
        extra = dict(
            num_experts=hf_config["num_local_experts"],
            expert_top_k=hf_config.get("num_experts_per_tok", 2),
            # HF Mixtral: MoE at every layer; our exports persist a
            # sparser placement (+ the capacity factor, a framework
            # knob HF has no field for) as extra config.json keys
            moe_every=hf_config.get("moe_every", 1),
            # HF MixtralConfig default (0.001), NOT our field default:
            # a missing key must not silently 20x the aux penalty
            router_aux_coef=hf_config.get("router_aux_loss_coef", 0.001),
            expert_capacity_factor=hf_config.get("expert_capacity_factor",
                                                 1.25),
        )
    if hf_config.get("attention_bias") or hf_config.get("mlp_bias"):
        raise ValueError(
            "attention_bias/mlp_bias=true (biased projections under "
            f"model_type {mt!r}) is not supported: the modules are "
            "bias-free (Qwen2's hardcoded q/k/v biases ARE supported "
            "via model_type 'qwen2') and the checkpoint's biases would "
            "be silently dropped")
    kw = dict(
        model_type=mt, sliding_window=window, qkv_bias=qkv_bias,
        sliding_window_start_layer=window_start, rope_scaling=rope_scaling,
        **extra,
        vocab_size=hf_config["vocab_size"],
        hidden_size=hf_config["hidden_size"],
        num_layers=hf_config["num_hidden_layers"],
        num_heads=hf_config["num_attention_heads"],
        num_kv_heads=hf_config.get("num_key_value_heads",
                                   hf_config["num_attention_heads"]),
        intermediate_size=hf_config["intermediate_size"],
        max_position_embeddings=hf_config.get("max_position_embeddings",
                                              2048),
        rope_theta=hf_config.get("rope_theta", 10000.0),
        rms_norm_eps=hf_config.get("rms_norm_eps", 1e-5),
        # HF's GemmaMLP substitutes gelu_pytorch_tanh whenever
        # hidden_activation is absent/null (the legacy 'gelu' configs of
        # the original release included) — honour that, not hidden_act
        hidden_act=(hf_config.get("hidden_activation")
                    or ("gelu_pytorch_tanh" if mt == "gemma"
                        else hf_config.get("hidden_act", "silu"))),
        # HF reads head_dim generically (Mistral-Nemo, Llama-3.x and
        # Qwen2 derivatives serialize non-default values too)
        head_dim=hf_config.get("head_dim"),
        initializer_range=hf_config.get("initializer_range", 0.02),
        # Gemma's CLASS default is tied (unlike Llama's), so an absent
        # key means tied there
        tie_word_embeddings=hf_config.get("tie_word_embeddings",
                                          mt == "gemma"),
        bos_token_id=hf_config.get("bos_token_id", 1),
        eos_token_id=hf_config.get("eos_token_id", 2),
        pad_token_id=(hf_config["pad_token_id"]
                      if hf_config.get("pad_token_id") is not None
                      else hf_config.get("eos_token_id", 2)),
    )
    kw.update(overrides)
    kw.pop("use_pooler", None)             # encoder-family knob
    # MoE-upcycling (num_experts override on a dense checkpoint):
    # LlamaConfig.__post_init__ coerces the model_type to 'mixtral' (or
    # rejects variants Mixtral's layout can't express) so the expert
    # bank survives the export round-trip.
    return LlamaConfig(**kw)


def _dense(cfg: LlamaConfig, features: int, name: str,
           use_bias: bool = False) -> nn.Module:
    from huggingface_sagemaker_tensorflow_distributed_tpu.models.quant import (
        make_dense,
    )

    return make_dense(cfg, features,
                      nn.initializers.normal(cfg.initializer_range),
                      use_bias=use_bias, name=name)


class LlamaRMSNorm(nn.Module):
    """HF ``LlamaRMSNorm``: fp32 mean-square island, scale applied in the
    compute dtype (the weight multiplies AFTER the cast, HF order)."""

    config: LlamaConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        init = (nn.initializers.zeros if cfg.rms_unit_offset
                else nn.initializers.ones)
        scale = self.param("scale", init, (x.shape[-1],), cfg.param_dtype)
        x32 = x.astype(jnp.float32)
        var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
        x32 = x32 * lax.rsqrt(var + cfg.rms_norm_eps)
        if cfg.rms_unit_offset:
            # Gemma order: (1 + w) multiplied in fp32, THEN cast down
            return (x32 * (1.0 + scale.astype(jnp.float32))).astype(
                cfg.dtype)
        return (x32.astype(cfg.dtype) * scale.astype(cfg.dtype))


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature ``0.1 * mscale * ln(factor) + 1``
    (1 without a factor above 1 or with ``mscale`` 0)."""
    if factor <= 1.0 or not mscale:
        return 1.0
    return 0.1 * mscale * math.log(factor) + 1.0


def yarn_correction_range(scaling: dict, dim: int, theta: float):
    """``(low, high)``: the rotary pair indices between which YaRN
    blends from kept to interpolated frequencies. ``d(n)`` is the index
    whose wavelength makes ``n`` turns over the original context."""
    old_len = scaling["original_max_position_embeddings"]

    def d(turns):
        return (dim * math.log(old_len / (turns * 2.0 * math.pi))
                / (2.0 * math.log(theta)))

    low = max(math.floor(d(scaling.get("beta_fast", 32))), 0)
    high = min(math.ceil(d(scaling.get("beta_slow", 1))), dim - 1)
    return low, high


def _scaled_inv_freq(inv_freq, scaling: Optional[dict],
                     theta: Optional[float] = None):
    """Apply HF rope_scaling to the base inverse frequencies.

    - "linear": inv_freq / factor (position interpolation);
    - "llama3": NTK-by-parts (HF ``_compute_llama3_parameters``) — long
      wavelengths (past the original context) are interpolated by
      ``factor``, short ones kept, the band between ``low_freq_factor``
      and ``high_freq_factor`` smoothly blended.

    - "yarn" (needs ``theta``): pairs below ``low`` keep their frequency,
      pairs above ``high`` are interpolated by ``factor``, a linear ramp
      between (:func:`yarn_correction_range`). Its attention temperature
      is the caller's (``yarn_mscale``): the DeepSeek-V2 family puts it
      into the softmax scale, and its cos/sin factor is
      ``mscale / mscale_all_dim``.

    "linear" and "llama3" have attention_factor 1.0 in HF, so cos/sin
    need no post-scaling. Unsupported types are rejected at config build.
    """
    if not scaling:
        return inv_freq
    rope_type = scaling.get("rope_type", scaling.get("type"))
    factor = scaling["factor"]
    if rope_type == "linear":
        return inv_freq / factor
    if rope_type == "yarn":
        half = inv_freq.shape[0]
        low, high = yarn_correction_range(scaling, 2 * half, theta)
        ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - low)
                        / max(high - low, 1e-3), 0.0, 1.0)
        return inv_freq / factor * ramp + inv_freq * (1.0 - ramp)
    low_f = scaling["low_freq_factor"]
    high_f = scaling["high_freq_factor"]
    old_len = scaling["original_max_position_embeddings"]
    wavelen = 2.0 * jnp.pi / inv_freq
    scaled = jnp.where(wavelen > old_len / low_f, inv_freq / factor,
                       inv_freq)
    smooth = (old_len / wavelen - low_f) / (high_f - low_f)
    smoothed = (1.0 - smooth) * scaled / factor + smooth * scaled
    mid = (wavelen >= old_len / high_f) & (wavelen <= old_len / low_f)
    return jnp.where(mid, smoothed, scaled)


def rope_tables(position_ids, head_dim: int, theta: float,
                scaling: Optional[dict] = None):
    """(cos, sin) [B, 1, S, D] in HF's duplicated-half layout — computed
    ONCE per forward (they depend only on positions) and threaded to
    every layer, as HF's rotary module does. ``scaling`` is the HF
    rope_scaling mapping (``LlamaConfig.rope_scaling_dict``)."""
    inv_freq = 1.0 / (theta ** (jnp.arange(0, head_dim, 2,
                                           dtype=jnp.float32) / head_dim))
    inv_freq = _scaled_inv_freq(inv_freq, scaling, theta)
    angles = position_ids.astype(jnp.float32)[:, :, None] * inv_freq
    cos = jnp.concatenate([jnp.cos(angles)] * 2, axis=-1)[:, None]
    sin = jnp.concatenate([jnp.sin(angles)] * 2, axis=-1)[:, None]
    return cos, sin


def apply_rope(x, rope):
    """HF rotate-half RoPE on [B, H, S, D] given precomputed tables."""
    cos, sin = rope
    d = x.shape[-1]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    rotated = jnp.concatenate([-x2, x1], axis=-1)
    return (x.astype(jnp.float32) * cos
            + rotated.astype(jnp.float32) * sin).astype(x.dtype)


def kv_quantize(x):
    """Symmetric per-(batch, head, slot) int8 quantization of a K or V
    slice [B, H, S, D]: scale = amax/127 over the head dim, zero rows
    keep scale 0 (dequant returns exact zeros). Returns (int8, fp32
    scale [B, H, S, 1])."""
    x32 = x.astype(jnp.float32)
    scale = jnp.max(jnp.abs(x32), axis=-1, keepdims=True) / 127.0
    q = jnp.where(scale > 0, x32 / jnp.where(scale > 0, scale, 1.0), 0.0)
    return jnp.clip(jnp.round(q), -127, 127).astype(jnp.int8), scale


def write_kv_cache(cached_k, cached_v, scales, k, v, cur, compute_dtype):
    """The ONE decode-cache storage protocol shared by every decoder
    family's attention (Llama family + GPT-2): per-row
    ``dynamic_update_slice`` writes of the new K/V at each row's write
    index ``cur`` [B]; when ``scales`` is a ``(k_scale, v_scale)``
    variable pair the values are stored int8 with per-(head, slot)
    fp32 scales and the returned buffers are dequantized to
    ``compute_dtype`` (the read fuses the dequant). Returns the FULL
    [B, H, max_len, D] key/value buffers for attention."""

    def row_write(buf, new, c):
        # buf [H, S, D], new [H, q, D], c scalar — one row's write
        return lax.dynamic_update_slice(buf, new, (0, c, 0))

    if scales is not None:
        k_scale, v_scale = scales
        qk, sk = kv_quantize(k)
        qv, sv = kv_quantize(v)
        cached_k.value = jax.vmap(row_write)(cached_k.value, qk, cur)
        cached_v.value = jax.vmap(row_write)(cached_v.value, qv, cur)
        k_scale.value = jax.vmap(row_write)(k_scale.value, sk, cur)
        v_scale.value = jax.vmap(row_write)(v_scale.value, sv, cur)
        k = (cached_k.value.astype(jnp.float32)
             * k_scale.value).astype(compute_dtype)
        v = (cached_v.value.astype(jnp.float32)
             * v_scale.value).astype(compute_dtype)
        return k, v
    k = jax.vmap(row_write)(cached_k.value, k, cur)
    v = jax.vmap(row_write)(cached_v.value, v, cur)
    cached_k.value, cached_v.value = k, v
    return k, v


def write_paged_kv(cached_k, cached_v, scales, block_tables, k, v, cur):
    """The paged-pool counterpart of :func:`write_kv_cache` — the ONE
    scatter-write protocol of the serve engine's fused decode path
    (``ops/pallas_paged_attention.py``). The cache variables hold BLOCK
    POOLS ``[num_blocks, block_size, H, D]`` instead of per-row dense
    buffers; ``k``/``v`` are one decode step's values [B, H, 1, D],
    written at logical position ``cur`` [B] of each row's
    ``block_tables`` [B, blocks]. With ``scales`` (a ``(k_scale,
    v_scale)`` pool-variable pair, [num_blocks, block_size, H, 1]
    fp32), values store int8 via :func:`kv_quantize` — bitwise the SAME
    quantization the dense int8 cache performs, which is what keeps
    paged serving token-exact against ``generate_causal`` under
    ``kv_cache_dtype='int8'``. Mutates the variables; the caller
    attends via ``ops.attention.paged_attention`` (the read fuses the
    dequant)."""
    from huggingface_sagemaker_tensorflow_distributed_tpu.ops.attention import (
        scatter_paged_kv,
    )

    if scales is not None:
        k_scale, v_scale = scales
        qk, sk = kv_quantize(k)
        qv, sv = kv_quantize(v)
        cached_k.value = scatter_paged_kv(
            cached_k.value, block_tables, cur, qk[:, :, 0, :])
        cached_v.value = scatter_paged_kv(
            cached_v.value, block_tables, cur, qv[:, :, 0, :])
        k_scale.value = scatter_paged_kv(
            k_scale.value, block_tables, cur, sk[:, :, 0, :])
        v_scale.value = scatter_paged_kv(
            v_scale.value, block_tables, cur, sv[:, :, 0, :])
        return
    cached_k.value = scatter_paged_kv(
        cached_k.value, block_tables, cur, k[:, :, 0, :])
    cached_v.value = scatter_paged_kv(
        cached_v.value, block_tables, cur, v[:, :, 0, :])


class LlamaAttention(nn.Module):
    """GQA self-attention with RoPE and an optional incremental KV cache
    (cached pre-repeat: [B, H_kv, max_len, D]; stored int8 + per-slot
    scales under ``kv_cache_dtype='int8'``). ``use_window`` applies
    the config's sliding window to THIS layer (per-layer policy)."""

    config: LlamaConfig
    use_window: bool = False
    # window via the attention kernel (banded flash tile-skipping) vs a
    # general additive mask: kernel banding indexes ROWS, which equals
    # logical positions only for default (arange) position_ids
    kernel_window: bool = False

    @nn.compact
    def __call__(self, hidden, attn_mask=None, rope=None,
                 position_ids=None, deterministic: bool = True,
                 decode: bool = False):
        cfg = self.config
        head_dim = cfg.resolved_head_dim
        B, S, _ = hidden.shape

        def split(x, n_heads):
            return x.reshape(B, S, n_heads, head_dim).transpose(0, 2, 1, 3)

        qb = cfg.qkv_bias
        q = _dense(cfg, cfg.num_heads * head_dim, "q_proj",
                   use_bias=qb)(hidden)
        k = _dense(cfg, cfg.num_kv_heads * head_dim, "k_proj",
                   use_bias=qb)(hidden)
        if cfg.qk_norm:
            q = LlamaRMSNorm(cfg, name="q_norm")(q)
            k = LlamaRMSNorm(cfg, name="k_norm")(k)
        q, k = split(q, cfg.num_heads), split(k, cfg.num_kv_heads)
        v = split(_dense(cfg, cfg.num_kv_heads * head_dim, "v_proj",
                         use_bias=qb)(hidden), cfg.num_kv_heads)

        if rope is not None:
            q = apply_rope(q, rope)
            k = apply_rope(k, rope)

        causal = True
        if decode:
            int8_kv = cfg.kv_cache_dtype == "int8"
            kv_store = jnp.int8 if int8_kv else k.dtype
            is_init = self.has_variable("cache", "cached_key")
            cached_k = self.variable("cache", "cached_key", jnp.zeros,
                                     k.shape, kv_store)
            cached_v = self.variable("cache", "cached_value", jnp.zeros,
                                     v.shape, kv_store)
            if int8_kv:
                scale_shape = k.shape[:3] + (1,)
                k_scale = self.variable("cache", "cached_key_scale",
                                        jnp.zeros, scale_shape, jnp.float32)
                v_scale = self.variable("cache", "cached_value_scale",
                                        jnp.zeros, scale_shape, jnp.float32)
            # PER-ROW write indices [B]: rows may sit at different
            # depths (speculative decode accepts a different number of
            # tokens per row) — writes are per-row dynamic_update_slices
            # and the step mask broadcasts per row
            cache_index = self.variable("cache", "cache_index",
                                        lambda: jnp.zeros((B,), jnp.int32))
            if self.has_variable("cache", "block_tables"):
                # serve paged-pool decode: the cache vars hold BLOCK
                # POOLS and a per-row block table (the engine's fused
                # kernel path). Scatter the new K/V (pre-repeat — the
                # kernel groups queries per kv head natively), then
                # fused paged attention walks the tables directly; the
                # sliding window bands in-kernel from logical positions
                # (serve contexts are contiguous, so slot == position)
                from huggingface_sagemaker_tensorflow_distributed_tpu.ops.attention import (
                    paged_attention,
                )

                if q.shape[2] != 1:
                    raise ValueError(
                        "paged decode is single-token (the fused kernel "
                        f"takes one query per slot, got q_len {q.shape[2]})")
                tables = self.get_variable("cache", "block_tables")
                cur = cache_index.value                   # [B]
                write_paged_kv(cached_k, cached_v,
                               (k_scale, v_scale) if int8_kv else None,
                               tables, k, v, cur)
                cache_index.value = cur + 1
                ctx = paged_attention(
                    q[:, :, 0, :], cached_k.value, cached_v.value,
                    tables, cur + 1, impl="pallas",
                    window=(cfg.sliding_window if self.use_window
                            else None),
                    k_scale_pool=k_scale.value if int8_kv else None,
                    v_scale_pool=v_scale.value if int8_kv else None)
                ctx = ctx.astype(cfg.dtype)[:, None, :, :]  # [B, 1, H, D]
                ctx = ctx.reshape(B, 1, cfg.num_heads * head_dim)
                return _dense(cfg, cfg.hidden_size, "o_proj")(ctx)
            if is_init:
                cur = cache_index.value                       # [B]
                max_len = cached_k.value.shape[2]
                q_len = q.shape[2]
                k, v = write_kv_cache(
                    cached_k, cached_v,
                    (k_scale, v_scale) if int8_kv else None, k, v, cur,
                    cfg.dtype)
                cache_index.value = cur + q_len
                key_pos = jnp.arange(max_len)[None, :]        # [1, S]
                qry_pos = (cur[:, None, None]
                           + jnp.arange(q_len)[None, :, None])  # [B, q, 1]
                valid = key_pos[None] <= qry_pos              # [B, q, S]
                step_mask = jnp.where(valid, 0.0, NEG_INF)[:, None]
                if cfg.sliding_window is not None and self.use_window:
                    # window in LOGICAL coordinates: buffer slots are not
                    # positions when the prompt is padded. Each valid
                    # slot's logical position is its rank among valid
                    # slots (the caller's buffer-validity mask), queries
                    # carry theirs in position_ids.
                    if attn_mask is not None:
                        valid_k = (attn_mask[:, 0, 0, :] > NEG_INF / 2)
                        key_logical = jnp.cumsum(
                            valid_k.astype(jnp.int32), axis=-1) - 1
                    else:
                        key_logical = jnp.broadcast_to(
                            jnp.arange(max_len), (B, max_len))
                    in_win = (key_logical[:, None, None, :]
                              > position_ids[:, None, :, None]
                              - cfg.sliding_window)
                    step_mask = step_mask + jnp.where(in_win, 0.0, NEG_INF)
                attn_mask = (step_mask if attn_mask is None
                             else attn_mask + step_mask)
                causal = False                 # the step mask IS causality

        if cfg.num_kv_heads != cfg.num_heads:
            rep = cfg.num_heads // cfg.num_kv_heads
            k = jnp.repeat(k, rep, axis=1)
            v = jnp.repeat(v, rep, axis=1)

        window = (cfg.sliding_window
                  if (self.use_window and self.kernel_window and not decode)
                  else None)
        ctx = dot_product_attention(q, k, v, mask=attn_mask,
                                    impl=cfg.attention_impl, causal=causal,
                                    window=window)
        b, h, s, d = ctx.shape
        ctx = ctx.transpose(0, 2, 1, 3).reshape(b, s, h * d)
        return _dense(cfg, cfg.hidden_size, "o_proj")(ctx)


class LlamaMlp(nn.Module):
    config: LlamaConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        act = ACT2FN[cfg.hidden_act]
        gate = _dense(cfg, cfg.intermediate_size, "gate_proj")(x)
        up = _dense(cfg, cfg.intermediate_size, "up_proj")(x)
        return _dense(cfg, cfg.hidden_size, "down_proj")(act(gate) * up)


class LlamaBlock(nn.Module):
    config: LlamaConfig
    use_window: bool = False
    kernel_window: bool = False
    layer_index: int = 0

    @nn.compact
    def __call__(self, hidden, masks=None, rope=None, position_ids=None,
                 deterministic: bool = True, decode: bool = False):
        from huggingface_sagemaker_tensorflow_distributed_tpu.models.layers import (
            is_moe_layer,
        )

        cfg = self.config
        plain, banded = masks if isinstance(masks, tuple) else (masks, None)
        attn_mask = banded if (self.use_window and banded is not None) \
            else plain
        attention = LlamaAttention(cfg, use_window=self.use_window,
                                   kernel_window=self.kernel_window,
                                   name="self_attn")
        if cfg.post_norm:
            attn = attention(hidden, attn_mask, rope, position_ids,
                             deterministic, decode)
            hidden = hidden + LlamaRMSNorm(cfg, name="post_attn_ln")(attn)
            return hidden + LlamaRMSNorm(cfg, name="post_mlp_ln")(
                LlamaMlp(cfg, name="mlp")(hidden))
        attn = attention(
            LlamaRMSNorm(cfg, name="input_ln")(hidden), attn_mask,
            rope, position_ids, deterministic, decode)
        hidden = hidden + attn
        normed = LlamaRMSNorm(cfg, name="post_attn_ln")(hidden)
        if cfg.num_experts and is_moe_layer(cfg, self.layer_index):
            from huggingface_sagemaker_tensorflow_distributed_tpu.models.moe import (
                MixtralMoeBlock,
            )

            mlp = MixtralMoeBlock(cfg, name="moe")(normed, deterministic)
        else:
            mlp = LlamaMlp(cfg, name="mlp")(normed)
        return hidden + mlp


class LlamaModel(nn.Module):
    """Backbone: embeddings + blocks + final RMSNorm. Returns
    (hidden, lm weight [V, H]) so the head can fuse with vocab-CE."""

    config: LlamaConfig

    @nn.compact
    def __call__(self, input_ids, attention_mask=None, position_ids=None,
                 deterministic: bool = True, decode: bool = False):
        cfg = self.config
        B, S = input_ids.shape
        default_positions = position_ids is None

        embed = nn.Embed(
            cfg.vocab_size, cfg.hidden_size,
            embedding_init=nn.initializers.normal(cfg.initializer_range),
            dtype=cfg.dtype, param_dtype=cfg.param_dtype,
            name="embed_tokens")

        if position_ids is None:
            offset = 0
            if decode:
                if cfg.sliding_window is not None and attention_mask is not None:
                    # windowed decode banding runs in LOGICAL coordinates
                    # (key positions from the mask cumsum); defaulted
                    # query positions would be buffer-slot offsets, which
                    # diverge on padded prompts — silently mis-windowing.
                    # generate_causal always passes mask-derived
                    # positions; require the same of any caller.
                    raise ValueError(
                        "decode with sliding_window and an attention_mask "
                        "requires explicit position_ids (logical query "
                        "positions, e.g. mask.cumsum(-1) - 1 at each "
                        "step): defaulted buffer-slot positions would "
                        "mis-window padded prompts")
                is_init = self.has_variable("cache", "position_index")
                idx = self.variable("cache", "position_index",
                                    lambda: jnp.array(0, jnp.int32))
                if is_init:
                    offset = idx.value
                    idx.value = offset + S
            position_ids = offset + jnp.arange(S)[None, :]
            position_ids = jnp.broadcast_to(position_ids, (B, S))

        additive_mask = (make_attention_mask(attention_mask)
                        if attention_mask is not None else None)
        banded_mask = None
        # ring shards the seq axis and has no banded schedule — it gets
        # the general banded mask (detected → XLA fallback) instead
        kernel_window = (cfg.sliding_window is not None and not decode
                         and default_positions
                         and cfg.attention_impl != "ring")
        if (cfg.sliding_window is not None and not decode
                and not kernel_window):
            # Mistral banding, built ONCE from absolute positions: key
            # allowed iff 0 <= pos_q - pos_k < window. The general
            # [B,1,S,S] mask routes attention onto the XLA path (flash
            # covers pure-causal only); the decode path windows its
            # cache mask inside LlamaAttention (logical coordinates).
            # Windowed layers (i >= sliding_window_start_layer, the HF
            # Qwen2 max_window_layers policy) get the banded mask;
            # earlier layers keep full causal attention.
            pq = position_ids[:, None, :, None]
            pk = position_ids[:, None, None, :]
            band = (pq - pk < cfg.sliding_window) & (pq >= pk)
            band_mask = jnp.where(band, 0.0, NEG_INF)
            banded_mask = (band_mask if additive_mask is None
                           else additive_mask + band_mask)
        rope = (rope_tables(position_ids, cfg.resolved_head_dim,
                            cfg.rope_theta, cfg.rope_scaling_dict)
                if cfg.use_rope else None)

        x = embed(input_ids)
        if cfg.embed_scale:
            # Gemma: normalizer in the embedding dtype (HF computes the
            # sqrt as a tensor of that dtype)
            x = x * jnp.asarray(cfg.hidden_size ** 0.5, x.dtype)
        if cfg.pipeline_stages:
            if decode:
                raise ValueError(
                    "pipeline_stages and incremental decode cannot "
                    "combine: the KV cache is stage-local state. Export "
                    "the pipelined checkpoint and reload it dense "
                    "(pipeline_stages=0) for generation")
            if cfg.num_experts:
                raise ValueError("pipeline_stages and num_experts cannot "
                                 "combine (pipelined MoE is not supported)")
            if cfg.sliding_window is not None:
                raise ValueError(
                    "pipeline_stages cannot combine with sliding_window "
                    "(Mistral/Qwen2): the per-layer window policy makes "
                    "stages heterogeneous, which the vmap-over-stages "
                    "GPipe schedule cannot express")
            if not default_positions:
                raise ValueError(
                    "pipeline_stages requires default position_ids: the "
                    "pipelined stack closes over batch-invariant RoPE "
                    "tables computed from arange positions")
            if cfg.weight_quant != "none":
                raise ValueError(
                    "pipeline_stages and weight_quant cannot combine "
                    "(int8 weight-only kernels are a decode-path "
                    "feature; the pipelined stack is training-only)")
            if cfg.attention_impl == "ring":
                raise ValueError(
                    "pipeline_stages cannot combine with attention_impl="
                    "'ring' (sequence parallelism): scale long sequences "
                    "with sp OR pipeline with pp, not both")
            from huggingface_sagemaker_tensorflow_distributed_tpu.models.pipeline import (
                PipelinedLlamaStack,
            )
            x = PipelinedLlamaStack(cfg, name="pipelined_layers")(
                x, additive_mask, deterministic)
            x = LlamaRMSNorm(cfg, name="final_ln")(x)
            return x, embed.embedding
        block_cls = LlamaBlock
        if cfg.remat:
            block_cls = nn.remat(LlamaBlock, static_argnums=(5, 6),
                                 policy=remat_policy(cfg.remat_policy))
        for i in range(cfg.num_layers):
            windowed = (cfg.sliding_window is not None
                        and i >= cfg.sliding_window_start_layer)
            x = block_cls(cfg, use_window=windowed,
                          kernel_window=kernel_window, layer_index=i,
                          name=f"layers_{i}")(
                x, (additive_mask, banded_mask), rope, position_ids,
                deterministic, decode)
        x = LlamaRMSNorm(cfg, name="final_ln")(x)
        return x, embed.embedding


class LlamaForCausalLM(nn.Module):
    """HF ``LlamaForCausalLM`` parity. Same call signature as
    ``Gpt2LMHeadModel`` so the causal-lm task loss, ``generate_causal``
    and ``predict.py`` drive it unchanged; ``hidden_and_embedding``
    feeds the fused vocab-CE kernel (tied or untied head)."""

    config: LlamaConfig

    def setup(self):
        cfg = self.config
        self.backbone = LlamaModel(cfg)
        if not cfg.tie_word_embeddings:
            # plain fp Dense on purpose: the output projection stays full
            # precision under int8 weight-only decode (models/quant.py
            # excludes LM heads — quantization error there lands directly
            # on the logits)
            self.lm_head = nn.Dense(
                cfg.vocab_size, use_bias=False, dtype=cfg.dtype,
                param_dtype=cfg.param_dtype,
                kernel_init=nn.initializers.normal(cfg.initializer_range),
                name="lm_head")

    def _head_weight(self, tied_weight):
        if self.config.tie_word_embeddings:
            return tied_weight
        # nn.Dense kernel is [H, V]; the fused-CE contract wants [V, H]
        return self.variables["params"]["lm_head"]["kernel"].T

    def __call__(self, input_ids, attention_mask=None, token_type_ids=None,
                 position_ids=None, deterministic: bool = True,
                 decode: bool = False):
        # token_type_ids accepted for trainer-signature parity
        hidden, tied = self.backbone(input_ids, attention_mask,
                                     position_ids, deterministic, decode)
        if self.config.tie_word_embeddings:
            logits = jnp.einsum("bsh,vh->bsv", hidden,
                                tied.astype(self.config.dtype))
        else:
            logits = self.lm_head(hidden)
        return logits.astype(jnp.float32)

    def hidden_and_embedding(self, input_ids, attention_mask=None,
                             token_type_ids=None, position_ids=None,
                             deterministic: bool = True):
        """(hidden [B, S, H], lm weight [V, H]) — the fused-CE path."""
        hidden, tied = self.backbone(input_ids, attention_mask,
                                     position_ids, deterministic, False)
        return hidden, self._head_weight(tied)
