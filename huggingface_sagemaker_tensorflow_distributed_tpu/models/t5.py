"""T5 encoder-decoder models (Flax).

TPU-native replacement for the seq2seq slice of the capability surface the
reference delegates to HF ``transformers`` (reference
``scripts/train.py:117`` loads any ``TFAutoModel*`` checkpoint; SURVEY.md
D7 lists T5 encoder-decoder + seq2seq-LM head as the breadth target).

Architecture parity with HF T5: RMSNorm (no mean subtraction, no bias),
pre-LN residual blocks, relative-position-bucket attention bias held by
the first block of each stack and shared down the stack, no attention
scaling (folded into init), ReLU or gated-GeLU FFN (t5 v1.0 / v1.1),
tied input/output embeddings with the ``d_model**-0.5`` logit scale.

Decode path: every attention module supports an incremental KV cache
(``"cache"`` variable collection, grown with ``lax.dynamic_update_slice``)
so autoregressive generation is O(T) per step with static shapes — the
XLA-friendly form of generation (no Python control flow inside the loop;
see ``models/generate.py``).

Module names (``query``/``key``/``value``/``attention_out``, ``wi``/``wo``,
``shared``) line up with the tensor-parallel rules in
``parallel/sharding.py`` — T5 shards over the same mesh axes as the
encoder-only families.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from huggingface_sagemaker_tensorflow_distributed_tpu.models.layers import (
    remat_policy,
)
from huggingface_sagemaker_tensorflow_distributed_tpu.ops.attention import (
    relative_position_bias,
    relative_position_bucket,  # bucket math shared with the ring kernel
    xla_attention,
)
from huggingface_sagemaker_tensorflow_distributed_tpu.parallel.ring_attention import (
    ring_attention_or_fallback,
)

NEG_INF = -1e9


@dataclass(frozen=True)
class T5Config:
    """T5 architecture hyperparameters (HF ``T5Config`` field parity)."""

    vocab_size: int = 32128
    d_model: int = 512
    d_kv: int = 64
    d_ff: int = 2048
    num_layers: int = 6
    num_decoder_layers: int = 6
    num_heads: int = 8
    relative_attention_num_buckets: int = 32
    relative_attention_max_distance: int = 128
    dropout_rate: float = 0.1
    layer_norm_epsilon: float = 1e-6
    feed_forward_proj: str = "relu"      # "relu" (t5) | "gated-gelu" (t5 v1.1)
    tie_word_embeddings: bool = True
    pad_token_id: int = 0
    eos_token_id: int = 1
    decoder_start_token_id: int = 0
    initializer_factor: float = 1.0
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32
    remat: bool = False
    remat_policy: str = "full"           # full | dots | dots_no_batch
    # "xla" (default) or "ring": with a seq mesh axis the ENCODER
    # self-attention runs sequence-parallel ring attention, re-tiling the
    # relative-position bias per ring step from global positions (the
    # full [S, S] bias never materializes). Decoder/cross/KV-cache paths
    # materialize the bias from the same table and run XLA —
    # numerics-identical (tests/test_t5_ring.py).
    attention_impl: str = "xla"
    # GPipe pipeline parallelism over BOTH stacks (models/pipeline.py::
    # PipelinedT5Stack): 0 = dense. Training/scoring path; generation
    # (KV cache) reloads dense like GPT-2's pipelined stack.
    pipeline_stages: int = 0
    pipeline_microbatches: int = 0
    # int8 weight-only dense kernels for generation (models/quant.py)
    weight_quant: str = "none"           # none | int8

    @property
    def is_gated_act(self) -> bool:
        return self.feed_forward_proj.startswith("gated-")

    @property
    def act_fn(self):
        act = self.feed_forward_proj.split("-")[-1]
        return {"relu": jax.nn.relu,
                "gelu": lambda x: jax.nn.gelu(x, approximate=True),
                "silu": jax.nn.silu}[act]


def t5_config_from_hf(hf_config: dict, **overrides) -> T5Config:
    """Map an HF T5Config dict (config.json) to our T5Config."""
    ff_proj = hf_config.get("feed_forward_proj", "relu")
    if hf_config.get("is_gated_act") and not ff_proj.startswith("gated-"):
        ff_proj = "gated-" + ff_proj
    kw = dict(
        vocab_size=hf_config["vocab_size"],
        d_model=hf_config["d_model"],
        d_kv=hf_config["d_kv"],
        d_ff=hf_config["d_ff"],
        num_layers=hf_config["num_layers"],
        num_decoder_layers=hf_config.get("num_decoder_layers",
                                         hf_config["num_layers"]),
        num_heads=hf_config["num_heads"],
        relative_attention_num_buckets=hf_config.get(
            "relative_attention_num_buckets", 32),
        relative_attention_max_distance=hf_config.get(
            "relative_attention_max_distance", 128),
        dropout_rate=hf_config.get("dropout_rate", 0.1),
        layer_norm_epsilon=hf_config.get("layer_norm_epsilon", 1e-6),
        feed_forward_proj=ff_proj,
        tie_word_embeddings=hf_config.get("tie_word_embeddings", True),
        pad_token_id=hf_config.get("pad_token_id", 0),
        eos_token_id=hf_config.get("eos_token_id", 1),
        decoder_start_token_id=hf_config.get("decoder_start_token_id", 0),
        initializer_factor=hf_config.get("initializer_factor", 1.0),
    )
    kw.update(overrides)
    return T5Config(**kw)


class RMSNorm(nn.Module):
    """T5 layernorm: scale-only RMS normalization, statistics in fp32."""

    config: T5Config

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],),
                           cfg.param_dtype)
        x32 = x.astype(jnp.float32)
        var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
        x32 = x32 * lax.rsqrt(var + cfg.layer_norm_epsilon)
        return (x32 * scale.astype(jnp.float32)).astype(cfg.dtype)




def _t5_dense(cfg, features: int, std: float, name: str) -> nn.Module:
    """T5's bias-free dense — fp or int8 via the shared chokepoint
    (``models/quant.py::make_dense``) — used by attention and FFN."""
    from huggingface_sagemaker_tensorflow_distributed_tpu.models.quant import (
        make_dense,
    )
    return make_dense(cfg, features, nn.initializers.normal(std),
                      use_bias=False, name=name)


class T5Attention(nn.Module):
    """Multi-head attention, T5 flavor: no bias, no sqrt(d) scaling,
    optional relative-position bias, optional incremental KV cache."""

    config: T5Config
    causal: bool = False
    has_rel_bias: bool = False

    def _dense(self, features: int, name: str) -> nn.Module:
        cfg = self.config
        # HF init: q scaled by (d_model * d_kv)^-0.5, k/v/o by d_model^-0.5;
        # the fine-tune path overwrites these with checkpoint weights anyway.
        return _t5_dense(cfg, features,
                         cfg.initializer_factor * cfg.d_model ** -0.5, name)

    def _rel_bias_embed(self) -> nn.Embed:
        """The ONE construction of the rel_bias embedding — xla mode
        gathers dense bias through it, ring mode materializes its raw
        table; both modes must create the identical param
        (tests/test_t5_ring.py::test_t5_ring_param_tree_matches_xla)."""
        cfg = self.config
        return nn.Embed(cfg.relative_attention_num_buckets, cfg.num_heads,
                        embedding_init=nn.initializers.normal(
                            cfg.initializer_factor * cfg.d_model ** -0.5),
                        dtype=jnp.float32, param_dtype=cfg.param_dtype,
                        name="rel_bias")

    def _position_bias(self, q_len: int, kv_len: int, offset=None):
        """[1, heads, q_len, kv_len] learned bias from bucketed relative
        positions. ``offset`` shifts query positions (decode with cache);
        a PER-ROW [B] offset (rows at different depths under speculative
        decode) yields a [B, heads, q_len, kv_len] bias. Uniform decode
        (generate/beam) also takes the per-row branch since cache_index
        is stored [B]; the extra cost is a batched bucket computation +
        embed gather at decode shapes (q=1, kv=target_len) — noise next
        to the step's matmuls, so no scalar fast path is kept."""
        cfg = self.config
        ctx = jnp.arange(q_len)[:, None]
        if offset is not None:
            off = jnp.asarray(offset)
            ctx = (ctx + off if off.ndim == 0
                   else ctx[None] + off[:, None, None])       # [B, q, 1]
        mem = jnp.arange(kv_len)[None, :]
        buckets = relative_position_bucket(
            mem - ctx, bidirectional=not self.causal,
            num_buckets=cfg.relative_attention_num_buckets,
            max_distance=cfg.relative_attention_max_distance)
        values = self._rel_bias_embed()(buckets)
        if values.ndim == 4:                                  # [B, q, kv, h]
            return values.transpose(0, 3, 1, 2)
        return values.transpose(2, 0, 1)[None]

    @nn.compact
    def __call__(self, hidden, kv_hidden=None, mask=None, position_bias=None,
                 deterministic: bool = True, decode: bool = False):
        """Returns (output, position_bias). ``mask`` is additive,
        broadcastable to [batch, heads, q_len, kv_len]."""
        cfg = self.config
        inner = cfg.num_heads * cfg.d_kv
        source = hidden if kv_hidden is None else kv_hidden

        def split(x):
            b, s, _ = x.shape
            return x.reshape(b, s, cfg.num_heads, cfg.d_kv).transpose(0, 2, 1, 3)

        q = split(self._dense(inner, "query")(hidden))
        k = split(self._dense(inner, "key")(source))
        v = split(self._dense(inner, "value")(source))

        cache_offset = None
        if decode and kv_hidden is None:
            # Incremental self-attention cache: full-length zero buffers are
            # created on the init pass; each decode step writes its k/v slice
            # at cache_index and attends to positions <= its own. Write
            # indices are PER-ROW [B] (the shared decoder-family protocol,
            # models/llama.py::write_kv_cache): rows may sit at different
            # depths under speculative decode.
            from huggingface_sagemaker_tensorflow_distributed_tpu.models.llama import (
                write_kv_cache,
            )

            B = q.shape[0]
            is_init = self.has_variable("cache", "cached_key")
            cached_k = self.variable("cache", "cached_key", jnp.zeros, k.shape, k.dtype)
            cached_v = self.variable("cache", "cached_value", jnp.zeros, v.shape, v.dtype)
            cache_index = self.variable("cache", "cache_index",
                                        lambda: jnp.zeros((B,), jnp.int32))
            if is_init:
                cur = cache_index.value                       # [B]
                max_len = cached_k.value.shape[2]
                q_len = q.shape[2]
                k, v = write_kv_cache(cached_k, cached_v, None, k, v, cur,
                                      k.dtype)
                cache_index.value = cur + q_len
                valid = jnp.arange(max_len)[None, None, :] <= (
                    cur[:, None, None] + jnp.arange(q_len)[None, :, None])
                step_mask = jnp.where(valid, 0.0, NEG_INF)[:, None]
                mask = step_mask if mask is None else mask + step_mask
                cache_offset = cur

        # ring mode (sequence parallelism): the first
        # block threads the RAW [num_buckets, heads] bias table (ndim 2)
        # instead of a materialized [1, h, q, k] bias, and the encoder
        # self-attention recomputes per-step bias tiles inside the ring —
        # the full [S, S] bias never exists. Decoder/cross/decode paths
        # (short target sequences, KV cache) materialize from the same
        # table and run XLA attention, numerics-identical.
        ring = cfg.attention_impl == "ring"
        if ring and position_bias is None and self.has_rel_bias:
            position_bias = self._rel_bias_embed()(
                jnp.arange(cfg.relative_attention_num_buckets))

        if ring and kv_hidden is None and not decode and not self.causal:
            # encoder self-attention: padding mask rides the ring, the
            # bias table is re-tiled per step from global positions
            rel_spec = (True, cfg.relative_attention_num_buckets,
                        cfg.relative_attention_max_distance)
            ctx = ring_attention_or_fallback(
                q, k, v, mask=mask, scale=1.0,
                rel_bias_table=position_bias,
                rel_bias_spec=rel_spec if position_bias is not None else None)
        else:
            if ring and position_bias is not None and position_bias.ndim == 2:
                # decoder self-attention block 0: densify the table ONCE
                # and thread the dense bias, exactly like xla mode (later
                # blocks and the per-decode-step offset reuse it as-is)
                ctx_pos = jnp.arange(q.shape[2])[:, None]
                if cache_offset is not None:
                    # per-row offsets don't reach this branch (ring decode
                    # advances uniformly); collapse [B] to its max — all
                    # equal on this path
                    ctx_pos = ctx_pos + jnp.max(cache_offset)
                position_bias = relative_position_bias(
                    position_bias, ctx_pos, jnp.arange(k.shape[2])[None, :],
                    bidirectional=not self.causal,
                    num_buckets=cfg.relative_attention_num_buckets,
                    max_distance=cfg.relative_attention_max_distance)
            if position_bias is None:
                if self.has_rel_bias and not ring:
                    position_bias = self._position_bias(
                        q.shape[2], k.shape[2], offset=cache_offset)
                else:
                    position_bias = jnp.zeros(
                        (1, cfg.num_heads, q.shape[2], k.shape[2]),
                        jnp.float32)
            bias = position_bias if mask is None else position_bias + mask
            ctx = xla_attention(q, k, v, mask=bias, scale=1.0)  # no sqrt(d)

        b, h, s, d = ctx.shape
        out = self._dense(cfg.d_model, "attention_out")(
            ctx.transpose(0, 2, 1, 3).reshape(b, s, h * d))
        return out, position_bias


class T5FeedForward(nn.Module):
    config: T5Config

    @nn.compact
    def __call__(self, x, deterministic: bool = True):
        cfg = self.config
        std_in = cfg.initializer_factor * cfg.d_model ** -0.5
        std_out = cfg.initializer_factor * cfg.d_ff ** -0.5

        def dense(features, std, name):
            return _t5_dense(cfg, features, std, name)

        if cfg.is_gated_act:
            gate = cfg.act_fn(dense(cfg.d_ff, std_in, "wi_0")(x))
            x = gate * dense(cfg.d_ff, std_in, "wi_1")(x)
        else:
            x = cfg.act_fn(dense(cfg.d_ff, std_in, "wi")(x))
        x = nn.Dropout(cfg.dropout_rate)(x, deterministic=deterministic)
        return dense(cfg.d_model, std_out, "wo")(x)


class T5Block(nn.Module):
    """Pre-LN residual block: self-attn (+ cross-attn in decoder) + FFN."""

    config: T5Config
    is_decoder: bool = False
    has_rel_bias: bool = False

    @nn.compact
    def __call__(self, hidden, attn_mask=None, enc_hidden=None, enc_mask=None,
                 position_bias=None, deterministic: bool = True,
                 decode: bool = False):
        cfg = self.config
        drop = nn.Dropout(cfg.dropout_rate)

        x = RMSNorm(cfg, name="attn_ln")(hidden)
        attn, position_bias = T5Attention(
            cfg, causal=self.is_decoder, has_rel_bias=self.has_rel_bias,
            name="self_attn")(x, mask=attn_mask, position_bias=position_bias,
                              deterministic=deterministic, decode=decode)
        hidden = hidden + drop(attn, deterministic=deterministic)

        if self.is_decoder:
            x = RMSNorm(cfg, name="cross_ln")(hidden)
            cross, _ = T5Attention(cfg, causal=False, has_rel_bias=False,
                                   name="cross_attn")(
                x, kv_hidden=enc_hidden, mask=enc_mask,
                deterministic=deterministic)
            hidden = hidden + drop(cross, deterministic=deterministic)

        x = RMSNorm(cfg, name="ffn_ln")(hidden)
        ff = T5FeedForward(cfg, name="ffn")(x, deterministic)
        hidden = hidden + drop(ff, deterministic=deterministic)
        return hidden, position_bias


class T5Stack(nn.Module):
    """Encoder or decoder stack over embedded inputs.

    The relative-position bias is computed by block 0 and threaded through
    the remaining blocks (HF parity: ``has_relative_attention_bias`` only
    on the first block of each stack).
    """

    config: T5Config
    is_decoder: bool = False

    @nn.compact
    def __call__(self, embeds, attn_mask=None, enc_hidden=None, enc_mask=None,
                 deterministic: bool = True, decode: bool = False):
        cfg = self.config
        hidden = nn.Dropout(cfg.dropout_rate)(embeds, deterministic=deterministic)
        n_layers = cfg.num_decoder_layers if self.is_decoder else cfg.num_layers
        block_cls = T5Block
        if cfg.remat:
            # bound module is arg 0: deterministic=6, decode=7
            block_cls = nn.remat(T5Block, static_argnums=(6, 7),
                                 policy=remat_policy(cfg.remat_policy))
        position_bias = None
        for i in range(n_layers):
            hidden, position_bias = block_cls(
                cfg, is_decoder=self.is_decoder, has_rel_bias=(i == 0),
                name=f"block_{i}")(
                hidden, attn_mask, enc_hidden, enc_mask, position_bias,
                deterministic, decode)
        hidden = RMSNorm(cfg, name="final_ln")(hidden)
        return nn.Dropout(cfg.dropout_rate)(hidden, deterministic=deterministic)


def _padding_mask(attention_mask, dtype=jnp.float32):
    """{0,1} [batch, kv_len] → additive [batch, 1, 1, kv_len]."""
    m = attention_mask[:, None, None, :].astype(dtype)
    return (1.0 - m) * NEG_INF


class T5ForConditionalGeneration(nn.Module):
    """Encoder-decoder LM: the seq2seq task head (summarization,
    translation — the reference's capability surface via HF TF T5).

    ``encode`` / ``decode`` are exposed as separate apply methods so
    generation runs the encoder once and the decoder incrementally with a
    KV cache (``models/generate.py``).
    """

    config: T5Config

    is_encoder_decoder = True

    def setup(self):
        cfg = self.config
        self.shared = nn.Embed(
            cfg.vocab_size, cfg.d_model,
            embedding_init=nn.initializers.normal(cfg.initializer_factor),
            dtype=cfg.dtype, param_dtype=cfg.param_dtype, name="shared")
        if cfg.pipeline_stages:
            from huggingface_sagemaker_tensorflow_distributed_tpu.models.pipeline import (
                PipelinedT5Stack,
            )
            self.encoder = PipelinedT5Stack(cfg, is_decoder=False,
                                            name="encoder")
            self.decoder = PipelinedT5Stack(cfg, is_decoder=True,
                                            name="decoder")
        else:
            self.encoder = T5Stack(cfg, is_decoder=False, name="encoder")
            self.decoder = T5Stack(cfg, is_decoder=True, name="decoder")
        if not cfg.tie_word_embeddings:
            self.lm_head = nn.Dense(
                cfg.vocab_size, use_bias=False, dtype=cfg.dtype,
                param_dtype=cfg.param_dtype,
                kernel_init=nn.initializers.normal(cfg.initializer_factor),
                name="lm_head")

    def encode(self, input_ids, attention_mask=None, deterministic: bool = True):
        if attention_mask is None:
            attention_mask = jnp.ones_like(input_ids)
        return self.encoder(self.shared(input_ids),
                            attn_mask=_padding_mask(attention_mask),
                            deterministic=deterministic)

    def _lm_logits(self, hidden):
        cfg = self.config
        if cfg.tie_word_embeddings:
            hidden = hidden * (cfg.d_model ** -0.5)
            return self.shared.attend(hidden.astype(cfg.dtype))
        return self.lm_head(hidden)

    def decode(self, decoder_input_ids, encoder_hidden, encoder_attention_mask=None,
               decoder_attention_mask=None, deterministic: bool = True,
               decode: bool = False):
        """Decoder forward → vocab logits. ``decode=True`` uses/updates the
        incremental cache (mask built from the cache index internally)."""
        if decode:
            self_mask = None  # cache supplies causal masking
        else:
            self_mask = self._teacher_forcing_mask(decoder_input_ids,
                                                   decoder_attention_mask)
        enc_mask = None
        if encoder_attention_mask is not None:
            enc_mask = _padding_mask(encoder_attention_mask)
        hidden = self.decoder(self.shared(decoder_input_ids),
                              attn_mask=self_mask, enc_hidden=encoder_hidden,
                              enc_mask=enc_mask, deterministic=deterministic,
                              decode=decode)
        return self._lm_logits(hidden)

    def __call__(self, input_ids, attention_mask=None, decoder_input_ids=None,
                 decoder_attention_mask=None, deterministic: bool = True):
        enc = self.encode(input_ids, attention_mask, deterministic)
        return self.decode(decoder_input_ids, enc, attention_mask,
                           decoder_attention_mask, deterministic)

    def seq2seq_hidden_and_embedding(self, input_ids, attention_mask=None,
                                     decoder_input_ids=None,
                                     decoder_attention_mask=None,
                                     deterministic: bool = True):
        """(pre-head decoder hidden [B, T, H] with the tied-head scaling
        already applied, LM weight [V, H]) — the fused vocab-CE path
        (``train/trainer.py::make_fused_seq2seq_loss``): ``hidden·Wᵀ``
        equals ``__call__``'s logits, but [B, T, V] never materializes."""
        cfg = self.config
        enc = self.encode(input_ids, attention_mask, deterministic)
        hidden = self.decoder(
            self.shared(decoder_input_ids),
            attn_mask=self._teacher_forcing_mask(decoder_input_ids,
                                                 decoder_attention_mask),
            enc_hidden=enc,
            enc_mask=_padding_mask(attention_mask)
            if attention_mask is not None else None,
            deterministic=deterministic)
        if cfg.tie_word_embeddings:
            return hidden * (cfg.d_model ** -0.5), self.shared.embedding
        return hidden, self.lm_head.variables["params"]["kernel"].T

    def _teacher_forcing_mask(self, decoder_input_ids,
                              decoder_attention_mask):
        dec_len = decoder_input_ids.shape[1]
        i = jnp.arange(dec_len)[:, None]
        j = jnp.arange(dec_len)[None, :]
        causal = jnp.where(j <= i, 0.0, NEG_INF)[None, None]
        if decoder_attention_mask is not None:
            return causal + _padding_mask(decoder_attention_mask)
        return causal


def shift_right(labels, decoder_start_token_id: int, pad_token_id: int = 0,
                ignore_id: int = -100):
    """Teacher-forcing inputs: [start, y_0, ..., y_{T-2}] with ignore-index
    labels mapped back to pad (HF ``_shift_right`` parity)."""
    labels = jnp.where(labels == ignore_id, pad_token_id, labels)
    start = jnp.full(labels.shape[:-1] + (1,), decoder_start_token_id,
                     labels.dtype)
    return jnp.concatenate([start, labels[..., :-1]], axis=-1)
