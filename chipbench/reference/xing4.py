"""Xing4 (``model_type`` xing4_0), full causal forward as the published
description computes it: plain ``jax.numpy`` in float32 under ``highest``
matmul precision, no cache, no kernels, no batching. Reads the program's
parameter tree by name and nothing else of it (the helpers it shares are
the DeepSeek-V2 reference's, beside this file: latent attention is the
same function in both families).

A token's hidden state is ``n = hc_mult`` residual streams ``x [n, C]``:
the embedding copied ``n`` times at the input, the streams summed behind
the last layer, then the final RMSNorm and the untied head. Every
sub-layer ``F`` (two a layer) is wrapped by manifold-constrained
hyper-connections (arXiv:2512.24880), :func:`_maps`::

    xbar = vec(x) / sqrt(mean(vec(x)^2) + hc_eps)             no learned weight
    hpre | hpost | hres = xbar Phi
    H_pre = sigmoid(a_pre hpre + b_pre)     H_post = 2 sigmoid(a_post hpost + b_post)
    M = exp(clip(a_res mat(hres) + b_res, lo, hi))
    20 times: every column of M divided by its sum + hc_eps, then every row
    u = H_pre x      y = F(u)      x+ = H_res x + H_post^T y

``F`` for attention is ``Attention(RMSNorm(u))``, latent attention in the
EXPANDED form only (``reference/deepseek_v2.py::_attention``: a masked
softmax over the whole sequence, rotary on adjacent pairs as published,
YaRN's frequencies and temperature). ``F`` for the FFN is a SwiGLU of
``RMSNorm(u)`` in the first ``first_k_dense_replace`` layers and after
them ``Shared(h) + sum_chosen w_i Expert_i(h)`` under the gate
(:func:`_gate`): ``s = sigmoid(h W_r)``, the experts chosen are the
``num_experts_per_tok`` largest of ``s + e_score_correction_bias`` (the
bias selects and never weighs), ``w_i = routed_scaling_factor * s_i / (sum
of the chosen s + 1e-20)``. Experts are computed one by one, EVERY expert
over EVERY token, each token's result taken by its gate weight for that
expert (zero where it did not choose it): nothing sorted, nothing
grouped, nothing dropped.

Departures from the published description, each for memory or for the cut
the configuration states, none changing a result:

- queries go in blocks of 512 and heads in groups of 16, so that the
  scores and the expanded keys of 3,072 tokens never exist whole;
- weights are upcast to float32 one layer (one expert) at a time, so the
  served bf16 weights need no float32 copy;
- the cut in depth: the configuration holds the first
  ``num_hidden_layers`` layers, and the final norm and the head read
  their output; the next-token-prediction module is absent (the main
  model's logits do not depend on it);
- the share: as in the DeepSeek-V2 reference, the gate runs over
  ``n_routed_experts * expert_parallel`` experts and the layer adds the
  held ones' part (this configuration holds them all: 64 x 1).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from chipbench.reference.deepseek_v2 import (
    _attention,
    _f32,
    _head,
    _mscale,
    _rms,
    _rounder,
    _swiglu,
    _yarn_inv_freq,
)


def _sinkhorn(m, iters: int, eps: float, rnd=lambda a: a):
    """Sinkhorn-Knopp, written out: columns first, then rows."""
    for _ in range(iters):
        m = rnd(m / (m.sum(axis=-2, keepdims=True) + eps))    # T_c
        m = rnd(m / (m.sum(axis=-1, keepdims=True) + eps))    # T_r
    return m


def _maps(x, hc, *, iters: int, eps: float, lo: float, hi: float,
          rnd=lambda a: a):
    """``(H_pre [S, n], H_post [S, n], H_res [S, n, n])`` of the streams
    ``x`` [S, n, C]. ``rnd`` (the control of the maps' precision) rounds
    the one matmul's operands and result, the exponential and every
    Sinkhorn iteration."""
    s, n, _ = x.shape
    flat = x.reshape(s, -1)
    xbar = flat / jnp.sqrt((flat * flat).mean(-1, keepdims=True) + eps)
    h = rnd(rnd(xbar) @ rnd(hc["phi"].astype(jnp.float32)))
    a = hc["alpha"].astype(jnp.float32)
    h_pre = jax.nn.sigmoid(a[0] * h[:, :n] + hc["b_pre"])
    h_post = 2.0 * jax.nn.sigmoid(a[1] * h[:, n:2 * n] + hc["b_post"])
    raw = a[2] * h[:, 2 * n:].reshape(s, n, n) + hc["b_res"]
    m = rnd(jnp.exp(jnp.clip(raw, lo, hi)))
    return h_pre, h_post, _sinkhorn(m, iters, eps, rnd)


def _wrapped(x, hc, fn, **kw):
    """``x+ = H_res x + H_post^T y`` of ``(y, aux) = F(H_pre x)``:
    ``(x+, aux, defect)``, the defect the largest ``|row or column sum -
    1|`` of any token's ``H_res``."""
    h_pre, h_post, h_res = _maps(x, hc, **kw)
    y, aux = fn(jnp.einsum("sn,snc->sc", h_pre, x))
    defect = jnp.maximum(jnp.abs(h_res.sum(-1) - 1.0).max(),
                         jnp.abs(h_res.sum(-2) - 1.0).max())
    return (jnp.einsum("smn,snc->smc", h_res, x)
            + h_post[:, :, None] * y[:, None, :]), aux, defect


def _gate(scores, bias, top_k: int, scale: float):
    """``(weights, chosen)`` [S, E]: float32 gate weights, zero outside a
    token's experts, and which those are. Chosen by ``scores + bias``,
    the ``top_k`` largest (ties: the lower index); weighed by the chosen
    ``scores`` alone, renormalised to sum to ``scale``."""
    sel = scores + bias
    i = jnp.arange(scores.shape[1])
    # rank of every expert: how many experts beat it
    beats = (sel[:, None, :] > sel[:, :, None]) | (
        (sel[:, None, :] == sel[:, :, None]) & (i[None, None, :]
                                                < i[None, :, None]))
    chosen = beats.sum(-1) < top_k
    kept = jnp.where(chosen, scores, 0.0)
    return scale * kept / (kept.sum(-1, keepdims=True) + 1e-20), chosen


def _ffn(h, lp, *, routing, rnd):
    """The FFN sub-layer of normed ``h`` [S, C]: ``(y, chosen)``."""
    if "mlp" in lp:
        m = _f32(lp["mlp"], rnd)
        return _swiglu(h, m["gate_proj"]["kernel"], m["up_proj"]["kernel"],
                       m["down_proj"]["kernel"], rnd), None
    top_k, factor, first = routing
    moe = lp["moe"]
    scores = rnd(jax.nn.sigmoid(
        rnd(h @ rnd(moe["router"].astype(jnp.float32)))))
    weights, chosen = _gate(
        scores, moe["e_score_correction_bias"].astype(jnp.float32), top_k,
        factor)
    weights = rnd(weights)
    held = moe["experts_gate_proj"].shape[0]
    held_w = jax.lax.dynamic_slice_in_dim(weights, first, held, axis=1)

    def one(acc, ew):      # one expert over every token, by its gate weight
        gate, up, down, w = ew
        return acc + w[:, None] * _swiglu(
            h, rnd(gate.astype(jnp.float32)), rnd(up.astype(jnp.float32)),
            rnd(down.astype(jnp.float32)), rnd), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(h), (
        moe["experts_gate_proj"], moe["experts_up_proj"],
        moe["experts_down_proj"], held_w.T))
    if "shared_experts" in moe:
        sh = _f32(moe["shared_experts"], rnd)
        y = y + _swiglu(h, sh["gate_proj"]["kernel"], sh["up_proj"]["kernel"],
                        sh["down_proj"]["kernel"], rnd)
    return y, chosen


@functools.partial(jax.jit, static_argnames=(
    "heads", "rank", "nope", "rot", "vd", "scale", "eps", "routing", "hc",
    "compute", "maps_compute"))
def _layer(x, lp, cos, sin, *, heads, rank, nope, rot, vd, scale, eps,
           routing, hc, compute=None, maps_compute=None):
    """One block over the streams ``x`` [S, n, C]: ``(x, chosen,
    defect)``, ``chosen`` [S, all experts] bool the experts the gate gave
    each token (None for a dense layer)."""
    rnd = _rounder(compute)
    iters, hc_eps, lo, hi = hc
    wrap = dict(iters=iters, eps=hc_eps, lo=lo, hi=hi,
                rnd=_rounder(maps_compute))
    with jax.default_matmul_precision("highest"):
        x, _, d_attn = _wrapped(x, lp["attn_hc"], lambda u: (_attention(
            rnd(_rms(u, lp["input_ln"]["scale"].astype(jnp.float32), eps)),
            _f32(lp["self_attn"], rnd), cos, sin, heads=heads, rank=rank,
            nope=nope, rot=rot, vd=vd, scale=scale, eps=eps, rnd=rnd), None),
            **wrap)
        x, chosen, d_ffn = _wrapped(x, lp["ffn_hc"], lambda u: _ffn(
            rnd(_rms(u, lp["post_attn_ln"]["scale"].astype(jnp.float32),
                     eps)), lp, routing=routing, rnd=rnd), **wrap)
        return x, chosen, jnp.maximum(d_attn, d_ffn)


def logits(params, config: dict, tokens, rows, compute=None,
           routing_out=None, maps_compute=None, defect_out=None):
    """Float32 logits ``[len(rows), vocab]`` at positions ``rows`` of the
    full causal forward over ``tokens`` ``[S]`` (``S`` at most 512, or a
    multiple of 512). ``config`` is the configuration FILE
    (``n_routed_experts`` counts the experts held, ``expert_parallel`` the
    shares, ``expert_rank`` which one this is). ``compute`` (a dtype's
    name) rounds every matmul operand, probability and gate weight of the
    sub-layers through that dtype (the reading a limit of the comparison
    is set against); ``maps_compute`` rounds the wrap's maps the same way
    (``xbar Phi``, the exponential, Sinkhorn) and nothing else.
    ``routing_out`` (a list) receives each expert layer's ``[S, all
    experts]`` bool of chosen experts, ``defect_out`` each layer's
    largest ``|row or column sum - 1|`` of an ``H_res``."""
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
    routing = (config["num_experts_per_tok"],
               float(config["routed_scaling_factor"]),
               int(config.get("expert_rank", 0)) * held)
    hc = (int(config["hc_sinkhorn_iters"]), float(config["hc_eps"]),
          float(config["mhc_h_res_clamp_min"]),
          float(config["mhc_h_res_clamp_max"]))
    n = int(config["hc_mult"])
    bb = params["backbone"]
    h = bb["embed_tokens"]["embedding"][tokens].astype(jnp.float32)
    x = jnp.broadcast_to(h[:, None, :], (h.shape[0], n, h.shape[1]))
    inv = _yarn_inv_freq(rot, float(config["rope_theta"]), scaling)
    ang = jnp.arange(tokens.shape[0], dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang) * factor, jnp.sin(ang) * factor
    for i in range(config["num_hidden_layers"]):
        x, chosen, defect = _layer(
            x, bb[f"layers_{i}"], cos, sin, heads=heads, rank=rank,
            nope=nope, rot=rot, vd=vd, scale=scale, eps=eps, routing=routing,
            hc=hc, compute=compute, maps_compute=maps_compute)
        if routing_out is not None and chosen is not None:
            routing_out.append(chosen)
        if defect_out is not None:
            defect_out.append(defect)
    rnd = _rounder(compute)
    return _head(rnd(x.sum(axis=1)[rows]), bb["final_ln"]["scale"],
                 rnd(params["lm_head"]["kernel"].astype(jnp.float32)),
                 eps=eps)
