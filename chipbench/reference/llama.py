"""Llama-layout decoder (``model_type`` qwen2: q/k/v biases, GQA, SwiGLU,
RMSNorm, rotate-half RoPE, tied embeddings), full causal forward, as HF's
``Qwen2ForCausalLM`` computes it: plain ``jax.numpy`` in float32 under
``highest`` matmul precision, no cache, no kernels, no batching. Reads
the program's parameter tree by name and nothing else of it. Weights are
upcast layer by layer, so the served bf16 weights need no float32 copy.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _rope(x, cos, sin):
    half = x.shape[-1] // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos + rot * sin


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "eps"))
def _layer(x, lp, cos, sin, *, heads, kv_heads, eps):
    with jax.default_matmul_precision("highest"):
        lp = _f32(lp)
        s = x.shape[0]
        a = lp["self_attn"]
        h = _rms(x, lp["input_ln"]["scale"], eps)
        d = a["q_proj"]["kernel"].shape[1] // heads

        def proj(name, n):
            y = h @ a[name]["kernel"] + a[name]["bias"]
            return y.reshape(s, n, d).transpose(1, 0, 2)        # [n, S, D]

        q = _rope(proj("q_proj", heads), cos, sin)
        k = _rope(proj("k_proj", kv_heads), cos, sin)
        v = proj("v_proj", kv_heads)
        rep = heads // kv_heads
        k, v = jnp.repeat(k, rep, axis=0), jnp.repeat(v, rep, axis=0)
        # queries in blocks of at most 512, so that the scores of a long
        # context never exist whole: [H, 512, S] at a time
        blk = min(s, 512)
        qb = q.reshape(heads, s // blk, blk, d).transpose(1, 0, 2, 3)
        starts = jnp.arange(s // blk) * blk

        def attend(args):
            qi, start = args
            scores = qi @ k.transpose(0, 2, 1) / jnp.sqrt(float(d))
            rows = start + jnp.arange(blk)[:, None]
            causal = jnp.arange(s)[None, :] <= rows
            scores = jnp.where(causal[None], scores, -1e30)
            return jax.nn.softmax(scores, axis=-1) @ v          # [H, blk, D]

        ctx = jax.lax.map(attend, (qb, starts))                 # [nb,H,blk,D]
        ctx = ctx.transpose(1, 0, 2, 3).reshape(heads, s, d)
        ctx = ctx.transpose(1, 0, 2).reshape(s, heads * d)
        x = x + ctx @ a["o_proj"]["kernel"]
        h = _rms(x, lp["post_attn_ln"]["scale"], eps)
        m = lp["mlp"]
        up = jax.nn.silu(h @ m["gate_proj"]["kernel"]) * (h @ m["up_proj"]["kernel"])
        return x + up @ m["down_proj"]["kernel"]


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, scale, table, *, eps):
    with jax.default_matmul_precision("highest"):
        return _rms(x, scale.astype(jnp.float32), eps) @ table.astype(
            jnp.float32).T


def logits(params, config: dict, tokens, rows):
    """Float32 logits ``[len(rows), vocab]`` at positions ``rows`` of the
    full causal forward over ``tokens`` ``[S]`` (``S`` at most 512, or a
    multiple of 512)."""
    heads = config["num_attention_heads"]
    kv_heads = config["num_key_value_heads"]
    eps = config["rms_norm_eps"]
    bb = params["backbone"]
    table = bb["embed_tokens"]["embedding"]
    x = table[tokens].astype(jnp.float32)
    d = config["hidden_size"] // heads
    inv = 1.0 / (config["rope_theta"] ** (
        jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(tokens.shape[0], dtype=jnp.float32)[:, None] * inv
    cos = jnp.concatenate([jnp.cos(ang)] * 2, axis=-1)[None]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, axis=-1)[None]
    for i in range(config["num_hidden_layers"]):
        x = _layer(x, bb[f"layers_{i}"], cos, sin, heads=heads,
                   kv_heads=kv_heads, eps=eps)
    if not config.get("tie_word_embeddings", False):
        table = params["lm_head"]["kernel"].T
    return _head(x[rows], bb["final_ln"]["scale"], table, eps=eps)
