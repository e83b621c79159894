"""Olmo-Hybrid (``model_type`` olmo_hybrid), full causal forward as the
published description computes it: plain ``jax.numpy`` in float32 under
``highest`` matmul precision, no cache, no kernels, no chunking, no
batching. Reads the program's parameter tree by name and nothing else of
it; imports nothing from ``models/`` or ``ops/``.

Every layer is the Olmo 2 / Olmo 3 block, ``h = x + RMSNorm(Mixer(x))``,
``out = h + RMSNorm(SwiGLU(h))``. ``layer_types`` says which mixer:

- ``linear_attention``: the Gated DeltaNet layer (Yang, Kautz &
  Hatamizadeh 2024, arXiv:2412.06464, as flash-linear-attention has it),
  per head::

      q~ = W_q x,  k~ = W_k x,  v~ = W_v x
      q, k, v = SiLU(causal depthwise conv, kernel K, no bias)
      q <- q / |q| * dk^-1/2,  k <- k / |k|
      beta = sigmoid(W_b x) (* 2 under linear_allow_neg_eigval)
      g = -exp(A_log) * softplus(W_a x + dt_bias),  alpha = exp(g)
      S_t = alpha_t S_{t-1} + k_t (x) [beta_t (v_t - (alpha_t S_{t-1})^T k_t)]
      o_t = S_t^T q_t
      y = W_o [RMSNorm_dv(o_t) * SiLU(W_g x)]

  the recurrence TOKEN BY TOKEN: one ``lax.scan`` over the positions,
  exactly the two lines above in a step, ``S`` float32 (or, for a control
  reading, rounded through ``state_dtype`` after every token).
- ``full_attention``: multi-head softmax attention, no bias, an RMSNorm
  over the whole query and the whole key projection before the heads are
  split, NO rotary embedding (the published ``rope_theta`` is null),
  causal mask over the whole sequence.

Departures from the published description, each for memory or stated in
the configuration's ``assumed``, none changing a result:

- queries of a full layer go in blocks of 512, so that the scores of a
  long context never exist whole;
- weights are upcast to float32 one layer at a time, so the served bf16
  weights need no float32 copy;
- the L2 norms carry flash-linear-attention's ``eps`` (``1e-6`` inside the
  root), which the equations above leave out;
- the depth: the configuration holds the model's first
  ``num_hidden_layers`` layers (a pipeline's first stage) with the
  embedding and the head; the final norm and the head read that stage's
  output.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

QUERY_BLOCK = 512


def _rounder(compute):
    """Identity, or (``compute`` names a dtype below float32) rounding
    through that dtype: ``logits(..., compute="float8_e4m3fn")`` is this
    forward with every matmul operand, probability and state read held in
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


def _l2(x):
    return x * jax.lax.rsqrt((x * x).sum(-1, keepdims=True) + 1e-6)


def _linear_mixer(x, a, *, heads, dk, dv, neg_eigval, eps, rnd, state_rnd):
    """``x`` [S, hidden] -> [S, hidden], the recurrence one token a step."""
    s = x.shape[0]
    pre = jnp.concatenate([x @ a["q_proj"]["kernel"],
                           x @ a["k_proj"]["kernel"],
                           x @ a["v_proj"]["kernel"]], axis=-1)   # [S, C]
    kernel = a["conv_kernel"]                                     # [K, C]
    width = kernel.shape[0]
    # y_t = sum_j kernel[j] * pre_{t - (K-1) + j}: zeros before the start
    padded = jnp.pad(pre, ((width - 1, 0), (0, 0)))
    conv = sum(padded[j:j + s] * kernel[j] for j in range(width))
    conv = jax.nn.silu(conv)
    q = _l2(conv[:, :heads * dk].reshape(s, heads, dk)) * dk ** -0.5
    k = _l2(conv[:, heads * dk:2 * heads * dk].reshape(s, heads, dk))
    v = conv[:, 2 * heads * dk:].reshape(s, heads, dv)
    q, k, v = rnd(q), rnd(k), rnd(v)
    beta = jax.nn.sigmoid(x @ a["b_proj"]["kernel"])              # [S, H]
    if neg_eigval:
        beta = 2.0 * beta
    g = -jnp.exp(a["A_log"]) * jax.nn.softplus(
        x @ a["a_proj"]["kernel"] + a["dt_bias"])
    alpha = jnp.exp(g)                                            # [S, H]

    def token(S, xs):                     # S [H, dk, dv]
        q_t, k_t, v_t, a_t, b_t = xs
        S = a_t[:, None, None] * S
        u = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", S, k_t))
        S = state_rnd(S + k_t[:, :, None] * u[:, None, :])
        return S, jnp.einsum("hkv,hk->hv", S, q_t)

    _, o = jax.lax.scan(token, jnp.zeros((heads, dk, dv), jnp.float32),
                        (q, k, v, alpha, beta))                   # [S, H, dv]
    o = _rms(o, a["o_norm_scale"], eps)
    o = o * jax.nn.silu((x @ a["g_proj"]["kernel"]).reshape(s, heads, dv))
    return rnd(o.reshape(s, heads * dv)) @ a["o_proj"]["kernel"]


def _full_mixer(x, a, *, heads, kv_heads, eps, rnd):
    """``x`` [S, hidden] -> [S, hidden]: masked softmax over the whole
    sequence, queries a block at a time."""
    s = x.shape[0]
    d = a["q_proj"]["kernel"].shape[1] // heads
    q = rnd(_rms(x @ a["q_proj"]["kernel"], a["q_norm"]["scale"], eps))
    k = rnd(_rms(x @ a["k_proj"]["kernel"], a["k_norm"]["scale"], eps))
    v = rnd(x @ a["v_proj"]["kernel"])
    q = q.reshape(s, heads, d).transpose(1, 0, 2)                 # [H, S, D]
    k = k.reshape(s, kv_heads, d).transpose(1, 0, 2)
    v = v.reshape(s, kv_heads, d).transpose(1, 0, 2)
    rep = heads // kv_heads
    k, v = jnp.repeat(k, rep, axis=0), jnp.repeat(v, rep, axis=0)
    blk = min(s, QUERY_BLOCK)
    qb = q.reshape(heads, s // blk, blk, d).transpose(1, 0, 2, 3)
    starts = jnp.arange(s // blk) * blk

    def attend(args):
        qi, start = args
        scores = qi @ k.transpose(0, 2, 1) / jnp.sqrt(float(d))
        rows = start + jnp.arange(blk)[:, None]
        scores = jnp.where((jnp.arange(s)[None, :] <= rows)[None], scores,
                           -1e30)
        return rnd(jax.nn.softmax(scores, axis=-1)) @ v           # [H, blk, D]

    ctx = jax.lax.map(attend, (qb, starts))                       # [nb,H,blk,D]
    ctx = ctx.transpose(1, 0, 2, 3).reshape(heads, s, d)
    ctx = ctx.transpose(1, 0, 2).reshape(s, heads * d)
    return rnd(ctx) @ a["o_proj"]["kernel"]


@functools.partial(jax.jit, static_argnames=(
    "heads", "kv_heads", "lin", "eps", "compute", "state_dtype"))
def _layer(x, lp, *, heads, kv_heads, lin, eps, compute=None,
           state_dtype=None):
    rnd, state_rnd = _rounder(compute), _rounder(state_dtype)
    with jax.default_matmul_precision("highest"):
        h = rnd(x)
        if "linear_attn" in lp:
            lin_heads, dk, dv, neg = lin
            # the decay's parameters and the convolution stay unrounded:
            # they are no matmul operands
            a = _f32(lp["linear_attn"])
            for name in ("q_proj", "k_proj", "v_proj", "a_proj", "b_proj",
                         "g_proj", "o_proj"):
                a[name] = _f32(a[name], rnd)
            mixed = _linear_mixer(h, a, heads=lin_heads, dk=dk, dv=dv,
                                  neg_eigval=neg, eps=eps, rnd=rnd,
                                  state_rnd=state_rnd)
        else:
            mixed = _full_mixer(h, _f32(lp["self_attn"], rnd), heads=heads,
                                kv_heads=kv_heads, eps=eps, rnd=rnd)
        x = x + _rms(mixed, lp["post_attn_ln"]["scale"].astype(jnp.float32),
                     eps)
        m = _f32(lp["mlp"], rnd)
        h = rnd(x)
        up = jax.nn.silu(h @ m["gate_proj"]["kernel"]) * (
            h @ m["up_proj"]["kernel"])
        return x + _rms(rnd(up) @ m["down_proj"]["kernel"],
                        lp["post_mlp_ln"]["scale"].astype(jnp.float32), eps)


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, scale, kernel, *, eps):
    with jax.default_matmul_precision("highest"):
        return _rms(x, scale.astype(jnp.float32), eps) @ kernel


def logits(params, config: dict, tokens, rows, compute=None,
           state_dtype=None):
    """Float32 logits ``[len(rows), vocab]`` at positions ``rows`` of the
    full causal forward over ``tokens`` ``[S]`` (``S`` at most 512, or a
    multiple of 512). ``compute`` (a dtype's name) rounds every matmul
    operand through that dtype (:func:`_rounder`); ``state_dtype`` rounds
    the recurrent state through that dtype after every token and nothing
    else."""
    eps = config["rms_norm_eps"]
    lin = (config["linear_num_key_heads"], config["linear_key_head_dim"],
           config["linear_value_head_dim"],
           bool(config["linear_allow_neg_eigval"]))
    bb = params["backbone"]
    kinds = config["layer_types"]
    x = bb["embed_tokens"]["embedding"][tokens].astype(jnp.float32)
    for i in range(config["num_hidden_layers"]):
        lp = bb[f"layers_{i}"]
        want = "linear_attn" if kinds[i] == "linear_attention" else "self_attn"
        if want not in lp:
            raise ValueError(f"layer {i} is {kinds[i]} in the file, the "
                             f"tree holds {sorted(lp)}")
        x = _layer(x, lp, heads=config["num_attention_heads"],
                   kv_heads=config["num_key_value_heads"], lin=lin, eps=eps,
                   compute=compute, state_dtype=state_dtype)
    rnd = _rounder(compute)
    if config.get("tie_word_embeddings", False):
        kernel = bb["embed_tokens"]["embedding"].astype(jnp.float32).T
    else:
        kernel = params["lm_head"]["kernel"].astype(jnp.float32)
    return _head(rnd(x[rows]), bb["final_ln"]["scale"], rnd(kernel), eps=eps)
