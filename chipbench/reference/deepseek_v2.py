"""DeepSeek-V2 (``model_type`` deepseek_v2), full causal forward as the
published model computes it: plain ``jax.numpy`` in float32 under
``highest`` matmul precision, no cache, no kernels, no batching. Reads the
program's parameter tree by name and nothing else of it.

Pre-norm residual blocks, RMSNorm, untied head. Attention is the EXPANDED
form only: ``cq = RMSNorm(x W_qa)``, ``q = cq W_qb`` split per head into
``q_nope | q_pe``; ``x W_kva`` split into ``ckv | k_pe`` (one ``k_pe`` for
all heads), ``c = RMSNorm(ckv)``; ``c W_kvb`` split per head into ``k_nope |
v``; ``score_h = (q_nope_h . k_nope_h + rope(q_pe_h) . rope(k_pe)) * s``.
RoPE rotates ADJACENT pairs ``(2i, 2i+1)`` as published, at YaRN's
frequencies (``_yarn_inv_freq``), and ``s`` carries YaRN's temperature.
Layers from ``first_k_dense_replace`` on are ``Shared(h) + sum_k w_k
Expert_k(h)`` under the group-limited gate (``_gate``), experts computed
one by one, EVERY held expert over EVERY token and masked by the gate:
nothing sorted, nothing grouped, nothing dropped.

Departures from the published description, each for memory or for the cut
the configuration states, none changing a result:

- queries go in blocks of 512 and heads in groups of 16, so that the
  scores and the expanded keys of a long context never exist whole;
- weights are upcast to float32 one layer (one expert) at a time, so the
  served bf16 weights need no float32 copy;
- the share: the configuration holds ``n_routed_experts`` of
  ``n_routed_experts * expert_parallel`` routed experts (those of rank
  ``expert_rank``) and a slice of the vocabulary. The gate runs over ALL
  experts; the layer adds ``Shared(h)`` and the held experts' part, and
  that partial result goes on, as the deployment's chip would compute it;
- the training-only auxiliary losses are absent.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

HEAD_GROUP = 16
QUERY_BLOCK = 512


def _rounder(compute):
    """Identity, or (``compute`` names a dtype below float32) rounding
    through that dtype: ``logits(..., compute="float8_e4m3fn")`` is this
    forward with every matmul operand, probability and gate weight held in
    that precision, the reading a limit of the comparison is set against
    (it has to come out as NOT correct; PERF.md)."""
    if compute is None:
        return lambda a: a
    dt = jnp.dtype(compute)
    return lambda a: a.astype(dt).astype(jnp.float32)


def _f32(tree, rnd=lambda a: a):
    return jax.tree_util.tree_map(lambda a: rnd(a.astype(jnp.float32)), tree)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def _mscale(factor, mscale):
    return 1.0 if factor <= 1 or not mscale else (
        0.1 * mscale * math.log(factor) + 1.0)


def _yarn_inv_freq(dim: int, theta: float, scaling):
    """Inverse frequencies of the ``dim / 2`` rotary pairs: ``f_i =
    theta^(-2i/dim)``; under YaRN pairs below ``low`` keep ``f_i``, pairs
    above ``high`` turn ``factor`` times slower, a linear ramp between,
    ``low = floor(d(beta_fast))``, ``high = ceil(d(beta_slow))``, ``d(n) =
    dim ln(original_len / 2 pi n) / (2 ln theta)``."""
    f = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    if not scaling:
        return f
    old = scaling["original_max_position_embeddings"]

    def d(n):
        return dim * math.log(old / (2 * math.pi * n)) / (2 * math.log(theta))

    low = max(math.floor(d(scaling["beta_fast"])), 0)
    high = min(math.ceil(d(scaling["beta_slow"])), dim - 1)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    return f / scaling["factor"] * ramp + f * (1.0 - ramp)


def _rope(x, cos, sin):
    """Rotate adjacent pairs ``(x[2i], x[2i+1])`` by the position's angle;
    ``x`` [..., S, D], ``cos``/``sin`` [S, D/2]."""
    a, b = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1)
    return out.reshape(x.shape)


def _gate(probs, n_group: int, topk_group: int, top_k: int, scale: float):
    """[S, E] float32 gate weights, zero outside a token's experts: the
    experts in ``n_group`` groups, a group scored by its best expert, the
    best ``topk_group`` groups kept, the ``top_k`` largest probabilities
    of those, times ``scale``, not renormalised. Ties: the lower index."""
    s, e = probs.shape
    size = e // n_group
    best = probs.reshape(s, n_group, size).max(-1)                # [S, G]
    # rank of every group: how many groups beat it (ties: lower index)
    g = jnp.arange(n_group)
    beats = (best[:, None, :] > best[:, :, None]) | (
        (best[:, None, :] == best[:, :, None]) & (g[None, None, :]
                                                  < g[None, :, None]))
    kept = beats.sum(-1) < topk_group                             # [S, G]
    left = jnp.where(jnp.repeat(kept, size, axis=1), probs, 0.0)
    i = jnp.arange(e)
    beats = (left[:, None, :] > left[:, :, None]) | (
        (left[:, None, :] == left[:, :, None]) & (i[None, None, :]
                                                  < i[None, :, None]))
    chosen = beats.sum(-1) < top_k                                # [S, E]
    return jnp.where(chosen, left, 0.0) * scale


def _swiglu(h, gate, up, down, rnd=lambda a: a):
    return rnd(jax.nn.silu(h @ gate) * (h @ up)) @ down


def _attention(x, a, cos, sin, *, heads, rank, nope, rot, vd, scale, eps,
               rnd):
    s = x.shape[0]
    cq = rnd(_rms(x @ a["q_a_proj"]["kernel"], a["q_a_ln"]["scale"], eps))
    q = rnd(cq @ a["q_b_proj"]["kernel"]).reshape(s, heads, nope + rot)
    q = q.transpose(1, 0, 2)                                      # [H, S, .]
    q_nope, q_pe = q[..., :nope], rnd(_rope(q[..., nope:], cos, sin))
    ckv = x @ a["kv_a_proj"]["kernel"]
    c = rnd(_rms(ckv[:, :rank], a["kv_a_ln"]["scale"], eps))      # [S, r]
    k_pe = rnd(_rope(ckv[:, rank:], cos, sin))                    # [S, rot]
    w = a["kv_b_proj"]                                            # [r, H, .]
    blk = min(s, QUERY_BLOCK)
    starts = jnp.arange(s // blk) * blk
    group = min(heads, HEAD_GROUP)
    out = []
    for h0 in range(0, heads, group):
        kv = rnd(jnp.einsum("sr,rhd->hsd", c, w[:, h0:h0 + group]))  # [g,S,.]
        k_nope, v = kv[..., :nope], kv[..., nope:]
        qn = q_nope[h0:h0 + group].reshape(group, s // blk, blk, nope)
        qp = q_pe[h0:h0 + group].reshape(group, s // blk, blk, rot)

        def attend(args):
            qn_i, qp_i, start = args                              # [g, blk, .]
            scores = (qn_i @ k_nope.transpose(0, 2, 1)
                      + qp_i @ k_pe.T[None]) * scale
            rows = start + jnp.arange(blk)[:, None]
            scores = jnp.where((jnp.arange(s)[None, :] <= rows)[None],
                               scores, -1e30)
            return rnd(jax.nn.softmax(scores, axis=-1)) @ v       # [g, blk, v]

        ctx = jax.lax.map(attend, (qn.transpose(1, 0, 2, 3),
                                   qp.transpose(1, 0, 2, 3), starts))
        out.append(ctx.transpose(1, 0, 2, 3).reshape(group, s, vd))
    ctx = jnp.concatenate(out, axis=0).transpose(1, 0, 2)         # [S, H, v]
    return rnd(ctx.reshape(s, heads * vd)) @ a["o_proj"]["kernel"]


@functools.partial(jax.jit, static_argnames=(
    "heads", "rank", "nope", "rot", "vd", "scale", "eps", "routing",
    "compute"))
def _layer(x, lp, cos, sin, *, heads, rank, nope, rot, vd, scale, eps,
           routing, compute=None):
    """One block: ``(x, chosen)``, ``chosen`` [S, all experts] bool the
    experts the gate gave each token (None for a dense layer)."""
    rnd = _rounder(compute)
    with jax.default_matmul_precision("highest"):
        x = x + _attention(
            rnd(_rms(x, lp["input_ln"]["scale"].astype(jnp.float32), eps)),
            _f32(lp["self_attn"], rnd), cos, sin, heads=heads, rank=rank,
            nope=nope, rot=rot, vd=vd, scale=scale, eps=eps, rnd=rnd)
        h = rnd(_rms(x, lp["post_attn_ln"]["scale"].astype(jnp.float32),
                     eps))
        if "mlp" in lp:
            m = _f32(lp["mlp"], rnd)
            return x + _swiglu(h, m["gate_proj"]["kernel"],
                               m["up_proj"]["kernel"],
                               m["down_proj"]["kernel"], rnd), None
        n_group, topk_group, top_k, factor, first = routing
        moe = lp["moe"]
        probs = rnd(jax.nn.softmax(
            rnd(h @ rnd(moe["router"].astype(jnp.float32))), -1))
        weights = _gate(probs, n_group, topk_group, top_k, factor)
        held = moe["experts_gate_proj"].shape[0]
        held_w = jax.lax.dynamic_slice_in_dim(weights, first, held, axis=1)

        def one(acc, ew):      # one expert over every token, gate-masked
            gate, up, down, w = ew
            return acc + w[:, None] * _swiglu(
                h, rnd(gate.astype(jnp.float32)),
                rnd(up.astype(jnp.float32)),
                rnd(down.astype(jnp.float32)), rnd), None

        y, _ = jax.lax.scan(one, jnp.zeros_like(x), (
            moe["experts_gate_proj"], moe["experts_up_proj"],
            moe["experts_down_proj"], held_w.T))
        if "shared_experts" in moe:
            sh = _f32(moe["shared_experts"], rnd)
            y = y + _swiglu(h, sh["gate_proj"]["kernel"],
                            sh["up_proj"]["kernel"],
                            sh["down_proj"]["kernel"], rnd)
        return x + y, weights > 0


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, scale, kernel, *, eps):
    with jax.default_matmul_precision("highest"):
        return _rms(x, scale.astype(jnp.float32), eps) @ kernel.astype(
            jnp.float32)


def logits(params, config: dict, tokens, rows, compute=None,
           routing_out=None):
    """Float32 logits ``[len(rows), vocab]`` at positions ``rows`` of the
    full causal forward over ``tokens`` ``[S]`` (``S`` at most 512, or a
    multiple of 512). ``config`` is the configuration FILE: its
    ``n_routed_experts`` counts the experts held, ``expert_parallel`` the
    shares, ``expert_rank`` which one this is. ``compute`` (a dtype's
    name) rounds every matmul operand through that dtype
    (:func:`_rounder`); ``routing_out`` (a list) receives each expert
    layer's ``[S, all experts]`` bool of chosen experts."""
    heads, eps = config["num_attention_heads"], config["rms_norm_eps"]
    rank, nope = config["kv_lora_rank"], config["qk_nope_head_dim"]
    rot, vd = config["qk_rope_head_dim"], config["v_head_dim"]
    scaling = config.get("rope_scaling")
    scale = (nope + rot) ** -0.5
    factor = 1.0
    if scaling:
        scale *= _mscale(scaling["factor"], scaling["mscale_all_dim"]) ** 2
        factor = (_mscale(scaling["factor"], scaling["mscale"])
                  / _mscale(scaling["factor"], scaling["mscale_all_dim"]))
    held = config["n_routed_experts"]
    shares = int(config.get("expert_parallel", 1))
    routing = (config["n_group"], config["topk_group"],
               config["num_experts_per_tok"],
               float(config["routed_scaling_factor"]),
               int(config.get("expert_rank", 0)) * held)
    bb = params["backbone"]
    router = next((bb[k]["moe"]["router"] for k in sorted(bb)
                   if k.startswith("layers_") and "moe" in bb[k]), None)
    if router is not None and router.shape[1] != held * shares:
        raise ValueError(f"router is {router.shape[1]} wide, the file says "
                         f"{held} x {shares}")
    x = bb["embed_tokens"]["embedding"][tokens].astype(jnp.float32)
    inv = _yarn_inv_freq(rot, float(config["rope_theta"]), scaling)
    ang = jnp.arange(tokens.shape[0], dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang) * factor, jnp.sin(ang) * factor
    for i in range(config["num_hidden_layers"]):
        x, chosen = _layer(x, bb[f"layers_{i}"], cos, sin, heads=heads,
                           rank=rank, nope=nope, rot=rot, vd=vd, scale=scale,
                           eps=eps, routing=routing, compute=compute)
        if routing_out is not None and chosen is not None:
            routing_out.append(chosen)
    rnd = _rounder(compute)
    return _head(rnd(x[rows]), bb["final_ln"]["scale"],
                 rnd(params["lm_head"]["kernel"].astype(jnp.float32)),
                 eps=eps)
