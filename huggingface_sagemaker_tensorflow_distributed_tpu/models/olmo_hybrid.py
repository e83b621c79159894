"""Olmo-Hybrid (``model_type`` ``olmo_hybrid``): a decoder whose layers
follow a per-layer pattern, ``layer_types``: ``linear_attention`` layers
mix tokens by the gated delta rule (``ops/gated_delta.py``; Gated DeltaNet,
arXiv:2412.06464, as flash-linear-attention's layer of that name computes
it), ``full_attention`` layers by multi-head softmax attention.

Both kinds sit in the Olmo 2 / Olmo 3 block, ``h = x + Norm(Mixer(x))``,
``out = h + Norm(MLP(h))`` (RMSNorm after each sub-layer, before the
residual add), with a SwiGLU MLP. A full layer IS ``models/llama.py``'s
block under three of its options (``post_norm``; ``qk_norm``, an RMSNorm
over the whole query and the whole key projection; ``use_rope=False``:
the published ``rope_theta`` is null and is taken at its word, position
comes from the recurrent layers), so its cache is the Llama attention's
(``cached_key`` / ``cached_value``, the serving engine's paged K/V pools
and fused paged kernel included).

A linear layer (:class:`GatedDeltaMixer`), per head of ``linear_num_heads``::

    q, k, v = SiLU(causal depthwise conv_K(W_q x | W_k x | W_v x))
    q <- q / |q| * dk^-1/2,  k <- k / |k|                  (float32)
    beta = sigmoid(W_b x) (* 2 under linear_allow_neg_eigval)
    g = -exp(A_log) * softplus(W_a x + dt_bias)            (float32)
    S_t = exp(g_t) S_{t-1} + k_t (x) [beta_t (v_t - (exp(g_t) S_{t-1})^T k_t)]
    y = W_o [RMSNorm_dv(S_t^T q_t) * SiLU(W_g x)]

Its decode cache is what the recurrence carries, indexed BY ROW and of a
size that does not grow with the context: ``recurrent_state`` ``[B, *
state_layout(H, dk, dv)]`` float32 (``ops/pallas_gated_delta.py``: the
one-token step's kernel owns the shape of what it carries, ``[B, H/G, dk,
G dv]`` where ``G`` heads side by side fill whole lane tiles and ``[B, H,
dk, dv]`` otherwise; a pure function of the shapes, the same on every
backend) and ``conv_state`` ``[B, K-1, H (2 dk + dv)]`` (the last
``K - 1`` rows before the convolution). The serving engine keeps both in
per-slot state pools (``serve/engine.py``: the ``state`` kind of its
cache plan). A one-token call runs the fused kernel on a TPU and the jnp
step elsewhere (:func:`state_step`); a run of tokens unpacks the rows it
was handed, runs the chunked form and packs them again. ``token_mask``
``[B, T]`` says which tokens are real: the real tokens of a row come
first, and what follows them (a prompt's pad tail, a pad row, an inactive
slot) advances neither the state nor the convolution's tail.

Out of scope: loading a published checkpoint (``models/convert.py`` has
no mapping for this family), training-side kernels (the backward of the
chunked scan runs as XLA differentiates it), left-padded batches through
the linear layers.
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from huggingface_sagemaker_tensorflow_distributed_tpu.models.llama import (
    LlamaBlock,
    LlamaConfig,
    LlamaMlp,
    LlamaRMSNorm,
    _dense,
)
from huggingface_sagemaker_tensorflow_distributed_tpu.ops import (
    pallas_gated_delta,
)
from huggingface_sagemaker_tensorflow_distributed_tpu.ops.attention import (
    make_attention_mask,
)
from huggingface_sagemaker_tensorflow_distributed_tpu.ops.gated_delta import (
    causal_conv,
    gated_delta_chunked,
    l2_normalize,
)

LINEAR, FULL = "linear_attention", "full_attention"


@dataclass(frozen=True)
class OlmoHybridConfig(LlamaConfig):
    """``LlamaConfig`` (the full layers and the MLP read it as it is) with
    the layer pattern and the linear layers' sizes."""

    model_type: str = "olmo_hybrid"
    qk_norm: bool = True
    use_rope: bool = False
    post_norm: bool = True
    layer_types: tuple = ()                # one of LINEAR | FULL a layer
    linear_num_heads: int = 30             # key heads == value heads
    linear_key_head_dim: int = 96
    linear_value_head_dim: int = 192
    linear_conv_kernel_dim: int = 4
    linear_allow_neg_eigval: bool = True

    def __post_init__(self):
        super().__post_init__()
        if len(self.layer_types) != self.num_layers:
            raise ValueError(
                f"layer_types names {len(self.layer_types)} layers, "
                f"num_hidden_layers is {self.num_layers}")
        unknown = sorted(set(self.layer_types) - {LINEAR, FULL})
        if unknown:
            raise ValueError(
                f"layer_types entry {unknown[0]!r} is not implemented "
                f"(supported: {LINEAR!r}, {FULL!r})")

    @property
    def linear_channels(self) -> int:
        """Channels the convolution runs over: q | k | v of all heads."""
        return self.linear_num_heads * (2 * self.linear_key_head_dim
                                        + self.linear_value_head_dim)


def olmo_hybrid_config_from_hf(hf_config: dict, **overrides) -> OlmoHybridConfig:
    """The program's configuration from a published ``config.json``
    mapping. Refuses by name what this code does not run, rather than load
    and diverge."""
    layer_types = hf_config.get("layer_types")
    if not layer_types:
        raise ValueError("olmo_hybrid needs layer_types: one of "
                         f"{LINEAR!r} | {FULL!r} a layer")
    heads = hf_config["linear_num_key_heads"]
    if hf_config.get("linear_num_value_heads", heads) != heads:
        raise ValueError(
            f"linear_num_value_heads {hf_config['linear_num_value_heads']} "
            f"!= linear_num_key_heads {heads} is not implemented: the "
            "recurrence here has one key head a value head")
    rope = hf_config.get("rope_parameters") or {}
    if rope.get("rope_theta") is not None or hf_config.get("rope_theta"):
        raise ValueError(
            "a rope_theta is not implemented for olmo_hybrid: the full "
            "layers here apply no rotary embedding (the published value "
            "is null)")
    if hf_config.get("attention_bias"):
        raise ValueError("attention_bias=true is not supported: the "
                         "projections are bias-free")
    kw = dict(
        layer_types=tuple(layer_types),
        linear_num_heads=heads,
        linear_key_head_dim=hf_config["linear_key_head_dim"],
        linear_value_head_dim=hf_config["linear_value_head_dim"],
        linear_conv_kernel_dim=hf_config.get("linear_conv_kernel_dim", 4),
        linear_allow_neg_eigval=bool(
            hf_config.get("linear_allow_neg_eigval", False)),
        vocab_size=hf_config["vocab_size"],
        hidden_size=hf_config["hidden_size"],
        num_layers=hf_config["num_hidden_layers"],
        num_heads=hf_config["num_attention_heads"],
        num_kv_heads=hf_config.get("num_key_value_heads",
                                   hf_config["num_attention_heads"]),
        intermediate_size=hf_config["intermediate_size"],
        max_position_embeddings=hf_config.get("max_position_embeddings",
                                              65536),
        rms_norm_eps=hf_config.get("rms_norm_eps", 1e-6),
        hidden_act=hf_config.get("hidden_act", "silu"),
        head_dim=hf_config.get("head_dim"),
        initializer_range=hf_config.get("initializer_range", 0.02),
        tie_word_embeddings=hf_config.get("tie_word_embeddings", False),
        # the dolma2 tokenizer's (only eos is read: a request ends on it)
        bos_token_id=hf_config.get("bos_token_id") or 100257,
        eos_token_id=hf_config.get("eos_token_id") or 100257,
        pad_token_id=(hf_config["pad_token_id"]
                      if hf_config.get("pad_token_id") is not None
                      else 100277),
    )
    kw.update(overrides)
    kw.pop("use_pooler", None)             # encoder-family knob
    return OlmoHybridConfig(**kw)


def state_form(q_len: int) -> str:
    """``step`` | ``chunked``: the form of the recurrence a call with
    ``q_len`` tokens a row runs (a pure function of the call's shape)."""
    return "step" if q_len == 1 else "chunked"


def state_step(cfg) -> str:
    """``kernel`` | ``xla``: how a one-token call of ``cfg``'s linear
    layers advances the state in this process
    (``pallas_gated_delta.state_step`` of the shapes and of the backend,
    asked as the other kernels' wrappers ask it)."""
    return pallas_gated_delta.state_step(
        cfg.linear_num_heads, cfg.linear_key_head_dim,
        cfg.linear_value_head_dim, platform=jax.devices()[0].platform)


def _a_log_init(key, shape, dtype=jnp.float32):
    # Gated DeltaNet: A ~ U(0, 16), stored as its logarithm
    return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1e-4, 16.0)
                   ).astype(dtype)


def _dt_bias_init(key, shape, dtype=jnp.float32):
    # Gated DeltaNet: dt log-uniform in [1e-3, 1e-1], stored as the
    # inverse of softplus so that softplus(dt_bias) = dt
    dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                    math.log(1e-3), math.log(1e-1)))
    return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)


def _conv_init(key, shape, dtype=jnp.float32):
    # a depthwise Conv1d's default: U(-1/sqrt(K), 1/sqrt(K))
    bound = shape[0] ** -0.5
    return jax.random.uniform(key, shape, jnp.float32, -bound, bound
                              ).astype(dtype)


class GatedDeltaMixer(nn.Module):
    """One linear-attention layer's token mixer (the module's text has
    the equations)."""

    config: OlmoHybridConfig

    @nn.compact
    def __call__(self, hidden, token_mask=None, decode: bool = False):
        cfg = self.config
        B, T, _ = hidden.shape
        H, dk, dv = (cfg.linear_num_heads, cfg.linear_key_head_dim,
                     cfg.linear_value_head_dim)
        K, C = cfg.linear_conv_kernel_dim, cfg.linear_channels
        x = jnp.concatenate([_dense(cfg, H * dk, "q_proj")(hidden),
                             _dense(cfg, H * dk, "k_proj")(hidden),
                             _dense(cfg, H * dv, "v_proj")(hidden)], axis=-1)
        a = _dense(cfg, H, "a_proj")(hidden)
        b = _dense(cfg, H, "b_proj")(hidden)
        gate = _dense(cfg, H * dv, "g_proj")(hidden)
        kernel = self.param("conv_kernel", _conv_init, (K, C),
                            cfg.param_dtype)
        # the decay's two parameters stay float32 whatever the weights are
        a_log = self.param("A_log", _a_log_init, (H,), jnp.float32)
        dt_bias = self.param("dt_bias", _dt_bias_init, (H,), jnp.float32)
        norm_scale = self.param("o_norm_scale", nn.initializers.ones, (dv,),
                                cfg.param_dtype)

        # the state as it is carried: packed (ops/pallas_gated_delta.py)
        state = jnp.zeros((B,) + pallas_gated_delta.state_layout(H, dk, dv),
                          jnp.float32)
        tail = jnp.zeros((B, K - 1, C), cfg.dtype)
        carried = False
        if decode:
            carried = self.has_variable("cache", "recurrent_state")
            state_var = self.variable("cache", "recurrent_state",
                                      lambda: state)
            tail_var = self.variable("cache", "conv_state", lambda: tail)
            if carried:
                state, tail = state_var.value, tail_var.value

        n_real = (None if token_mask is None
                  else jnp.sum(token_mask, axis=-1, dtype=jnp.int32))
        y, tail = causal_conv(x, tail, kernel, n_real)
        y = jax.nn.silu(y)                                    # float32
        q = l2_normalize(y[..., :H * dk].reshape(B, T, H, dk)) * dk ** -0.5
        k = l2_normalize(y[..., H * dk:2 * H * dk].reshape(B, T, H, dk))
        v = y[..., 2 * H * dk:].reshape(B, T, H, dv)
        beta = jax.nn.sigmoid(b.astype(jnp.float32))
        if cfg.linear_allow_neg_eigval:
            beta = beta * 2.0
        g = -jnp.exp(a_log) * jax.nn.softplus(a.astype(jnp.float32) + dt_bias)
        if state_form(T) == "step":
            o, state = pallas_gated_delta.gated_delta_step_carried(
                q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], state,
                None if token_mask is None else token_mask[:, 0],
                form=state_step(cfg))
            o = o[:, None]
        else:
            o, state = gated_delta_chunked(
                q, k, v, g, beta, pallas_gated_delta.unpack(state, H),
                token_mask)
            state = pallas_gated_delta.pack(state)
        if carried:
            state_var.value, tail_var.value = state, tail
        # gated RMSNorm per head, float32: norm(o) * scale * SiLU(gate)
        o = o * lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                          + cfg.rms_norm_eps)
        o = (o * norm_scale.astype(jnp.float32)
             * jax.nn.silu(gate.astype(jnp.float32).reshape(B, T, H, dv)))
        return _dense(cfg, cfg.hidden_size, "o_proj")(
            o.reshape(B, T, H * dv).astype(cfg.dtype))


class LinearBlock(nn.Module):
    """The post-norm block around a :class:`GatedDeltaMixer`."""

    config: OlmoHybridConfig

    @nn.compact
    def __call__(self, hidden, token_mask=None, decode: bool = False):
        cfg = self.config
        hidden = hidden + LlamaRMSNorm(cfg, name="post_attn_ln")(
            GatedDeltaMixer(cfg, name="linear_attn")(hidden, token_mask,
                                                     decode))
        return hidden + LlamaRMSNorm(cfg, name="post_mlp_ln")(
            LlamaMlp(cfg, name="mlp")(hidden))


class OlmoHybridModel(nn.Module):
    config: OlmoHybridConfig

    @nn.compact
    def __call__(self, input_ids, attention_mask=None, position_ids=None,
                 deterministic: bool = True, decode: bool = False,
                 token_mask=None):
        cfg = self.config
        mask = (make_attention_mask(attention_mask)
                if attention_mask is not None else None)
        if token_mask is None and attention_mask is not None and not decode:
            # the plain forward of a right-padded batch
            token_mask = attention_mask > 0
        x = nn.Embed(
            cfg.vocab_size, cfg.hidden_size,
            embedding_init=nn.initializers.normal(cfg.initializer_range),
            dtype=cfg.dtype, param_dtype=cfg.param_dtype,
            name="embed_tokens")(input_ids)
        for i, kind in enumerate(cfg.layer_types):
            if kind == LINEAR:
                x = LinearBlock(cfg, name=f"layers_{i}")(x, token_mask,
                                                         decode)
            else:
                x = LlamaBlock(cfg, layer_index=i, name=f"layers_{i}")(
                    x, (mask, None), None, position_ids, deterministic,
                    decode)
        return LlamaRMSNorm(cfg, name="final_ln")(x)


class OlmoHybridForCausalLM(nn.Module):
    """Same call signature as ``LlamaForCausalLM`` (so ``generate_causal``
    and the serving engine drive it unchanged), plus ``token_mask``
    ``[B, T]``: which tokens are real, for the linear layers' state, and
    ``logit_positions`` ``[B]``: the one position a row whose logits are
    wanted (the result is then ``[B, 1, V]``: a prefill chunk needs the
    head at its last real token, not at all 512, and 4 x 512 rows of a
    100,352-wide float32 head are 0.8 GB the chip does not have beside
    this model's weights, pools and state)."""

    config: OlmoHybridConfig

    state_form = staticmethod(state_form)
    takes_logit_positions = True

    def state_step(self) -> str:
        """:func:`state_step` for this model in this process: what the
        serving engine writes beside ``state_form`` on a decode step."""
        return state_step(self.config)

    def setup(self):
        cfg = self.config
        self.backbone = OlmoHybridModel(cfg)
        if not cfg.tie_word_embeddings:
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
                               deterministic, decode, token_mask)
        if logit_positions is not None:
            hidden = jnp.take_along_axis(
                hidden, logit_positions[:, None, None], axis=1)
        if self.config.tie_word_embeddings:
            table = self.backbone.variables["params"]["embed_tokens"][
                "embedding"]
            logits = jnp.einsum("bsh,vh->bsv", hidden,
                                table.astype(self.config.dtype))
        else:
            logits = self.lm_head(hidden)
        return logits.astype(jnp.float32)
