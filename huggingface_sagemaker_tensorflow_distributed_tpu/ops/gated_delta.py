"""The gated delta rule (Gated DeltaNet: Yang, Kautz & Hatamizadeh 2024,
arXiv:2412.06464) in plain ``jax.numpy`` / ``lax``: the recurrence a
linear-attention layer carries instead of keys and values.

Per head, with a state ``S`` of ``[dk, dv]`` float32::

    S_t = a_t S_{t-1} + k_t (x) [ b_t ( v_t - (a_t S_{t-1})^T k_t ) ]
    o_t = S_t^T q_t                    a_t = exp(g_t),  g_t <= 0

Three functions, all float32 inside whatever they are handed:

- :func:`gated_delta_step`: ONE token a row (a decode step). Elementwise
  over ``S``: two passes read it and one writes it (the projections of
  ``S`` on ``k`` and ``q`` together, then decay and rank-one update in
  one). On a TPU a decode step runs ``ops/pallas_gated_delta.py`` instead,
  which reads a slot's state once and writes it once; this form is what a
  CPU runs and the reference that kernel is tested against.
- :func:`gated_delta_chunked`: a run of tokens a row (a prefill chunk, the
  plain forward) in chunks of :data:`CHUNK`: inside a chunk the WY / UT
  transform (``(I + tril(K_b K^T * D, -1))^-1`` by a blocked forward
  substitution) turns the ``CHUNK`` sequential rank-one updates into matmuls, and
  a ``lax.scan`` over the chunks carries ``S``. Any length: the tail of the
  last chunk is padded with tokens that do nothing. On a TPU a prefill
  dispatch runs ``ops/pallas_gated_delta.py::gated_delta_chunked_packed``
  instead, which walks a row's chunks in fast memory on the state as it is
  carried; this form is what a CPU runs, what a gradient recomputes
  through, and the reference that kernel is tested against.
- :func:`causal_conv`: the depthwise causal convolution in front of the
  recurrence, with the last ``K - 1`` pre-convolution rows carried as a
  tail.

A token that must not advance the state (the pad tail of a prompt's last
chunk, a pad row of a batched dispatch, an inactive slot's decode step) is
masked: ``a = 1``, ``b = 0`` there, and the convolution's tail is taken at
the last real token. A masked token's ``q``/``k``/``v`` reach nothing: the
state and the tail come out as if it had not been there (bit for bit for
a row that is masked whole).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

CHUNK = 64
# every product of the chunked form in true float32: on a TPU a float32
# matmul is one bf16 pass unless told otherwise, and the state is carried
# over thousands of tokens
_PRECISION = lax.Precision.HIGHEST


def l2_normalize(x, eps: float = 1e-6):
    """``x / sqrt(sum(x^2) + eps)`` over the last axis, in float32 (the
    ``eps`` inside the root as flash-linear-attention's ``l2norm`` has
    it)."""
    x32 = x.astype(jnp.float32)
    return x32 * lax.rsqrt(jnp.sum(x32 * x32, axis=-1, keepdims=True) + eps)


def causal_conv(x, tail, kernel, n_real: Optional[jax.Array] = None):
    """Depthwise causal convolution over time. ``x`` ``[B, T, C]`` (before
    the convolution), ``tail`` ``[B, K-1, C]`` the ``K - 1`` rows that came
    before ``x`` (zeros at a sequence's start), ``kernel`` ``[K, C]``
    (``kernel[K-1]`` weighs the current token). Returns ``(y [B, T, C]
    float32, new tail [B, K-1, C]`` in ``tail``'s type``)``: the tail after
    the first ``n_real[b]`` tokens of row ``b`` (all ``T`` without
    ``n_real``; ``0`` hands the old tail back)."""
    T, K = x.shape[1], kernel.shape[0]
    seq = jnp.concatenate([tail.astype(x.dtype), x], axis=1)   # [B, T+K-1, C]
    seq32 = seq.astype(jnp.float32)
    w = kernel.astype(jnp.float32)
    y = sum(seq32[:, j:j + T] * w[j] for j in range(K))
    if n_real is None:
        new_tail = seq[:, T:]
    else:
        new_tail = jax.vmap(
            lambda row, n: lax.dynamic_slice_in_dim(row, n, K - 1, axis=0)
        )(seq, n_real.astype(jnp.int32))
    return y, new_tail.astype(tail.dtype)


def _masked(g, beta, mask):
    if mask is None:
        return g.astype(jnp.float32), beta.astype(jnp.float32)
    m = mask[..., None]
    return (jnp.where(m, g.astype(jnp.float32), 0.0),
            jnp.where(m, beta.astype(jnp.float32), 0.0))


def gated_delta_step(q, k, v, g, beta, state, mask=None):
    """One token a row. ``q``, ``k`` ``[B, H, dk]`` (normalised and scaled
    by the caller), ``v`` ``[B, H, dv]``, ``g``, ``beta`` ``[B, H]``,
    ``state`` ``[B, H, dk, dv]`` float32, ``mask`` ``[B]`` bool (False: the
    row's state stays as it was). Returns ``(o [B, H, dv] float32,
    state)``."""
    g, beta = _masked(g, beta, mask)
    q32, k32, v32 = (a.astype(jnp.float32) for a in (q, k, v))
    decay = jnp.exp(g)[..., None, None]
    # (a S)^T k and (a S)^T q in one pass over the state, the decay put on
    # the projections: a decayed copy of the state would be written and
    # read twice more (as XLA left it: 5 passes over S a layer a step,
    # 16.8 ms of a 32.4 ms step at 64 slots; chip run, PR 33)
    kq = jnp.stack([k32, q32], axis=-2)                       # [B, H, 2, dk]
    proj = decay * jnp.sum(state[:, :, None] * kq[..., None], axis=-2)
    u = beta[..., None] * (v32 - proj[:, :, 0])               # [B, H, dv]
    new_state = state * decay + k32[..., None] * u[..., None, :]
    # S_t^T q = (a S)^T q + (k . q) u
    o = proj[:, :, 1] + jnp.sum(k32 * q32, axis=-1, keepdims=True) * u
    return o, new_state


def _solve_unit_lower(a, rhs, block: int = 16):
    """``(I + a)^-1 rhs`` for ``a`` ``[..., C, C]`` strictly lower
    triangular, ``rhs`` ``[..., C, n]``, by forward substitution in blocks
    (flash-linear-attention's ``solve_tril`` scheme): the ``block``-wide
    diagonal blocks are inverted row by row (``block`` small steps, all
    blocks of all chunks at once), the blocks below the diagonal are
    matmuls. A generic triangular solve is one long sequential program a
    chunk on the backends here (the CPU's took 2.4 s of a tiny model's
    2.5 s prefill)."""
    C = a.shape[-1]
    if C % block:
        block = C
    nb = C // block
    lead = a.shape[:-2]
    blocks = a.reshape(*lead, nb, block, nb, block)
    diag = jnp.stack([blocks[..., i, :, i, :] for i in range(nb)], axis=-3)
    # rows of (I + diag)^-1: t_i = e_i - sum_{j < i} diag[i, j] t_j
    eye = jnp.eye(block, dtype=a.dtype)
    rows = [jnp.broadcast_to(eye[0], diag.shape[:-2] + (block,))]
    for i in range(1, block):
        rows.append(eye[i] - jnp.einsum(
            "...j,...jn->...n", diag[..., i, :i], jnp.stack(rows, axis=-2),
            precision=_PRECISION))
    inv = jnp.stack(rows, axis=-2)                    # [..., nb, block, block]
    r = rhs.reshape(*lead, nb, block, rhs.shape[-1])
    out = []
    for i in range(nb):
        r_i = r[..., i, :, :]
        for j in range(i):
            r_i = r_i - jnp.einsum("...ik,...kn->...in",
                                   blocks[..., i, :, j, :], out[j],
                                   precision=_PRECISION)
        out.append(jnp.einsum("...ik,...kn->...in", inv[..., i, :, :], r_i,
                              precision=_PRECISION))
    return jnp.concatenate(out, axis=-2)


def gated_delta_chunked(q, k, v, g, beta, state, mask=None,
                        chunk: int = CHUNK):
    """A run of tokens a row. ``q``, ``k`` ``[B, T, H, dk]`` (normalised
    and scaled by the caller), ``v`` ``[B, T, H, dv]``, ``g``, ``beta``
    ``[B, T, H]``, ``state`` ``[B, H, dk, dv]`` float32, ``mask`` ``[B, T]``
    bool (False: the token does nothing). Returns ``(o [B, T, H, dv]
    float32, state)``. Equal to ``T`` calls of :func:`gated_delta_step`
    up to float32 rounding."""
    B, T, H, dk = q.shape
    dv = v.shape[-1]
    g, beta = _masked(g, beta, mask)
    pad = -T % chunk
    N = (T + pad) // chunk

    def chunks(a):           # [B, T, H, ...] -> [N, B, H, chunk, ...]
        a = a.astype(jnp.float32)
        if pad:
            a = jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
        a = a.reshape(B, N, chunk, *a.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(a, 3, 1), 2, 0)

    q, k, v, g, beta = (chunks(a) for a in (q, k, v, g, beta))
    gc = jnp.cumsum(g, axis=-1)                               # [N, B, H, C]
    idx = jnp.arange(chunk)
    lower = idx[:, None] >= idx[None, :]
    # D[i, j] = exp(gc_i - gc_j) for i >= j: the decay from token j to i
    decay = jnp.exp(jnp.where(lower, gc[..., :, None] - gc[..., None, :],
                              -jnp.inf))
    kb = k * beta[..., None]
    a = jnp.einsum("...ik,...jk->...ij", kb, k, precision=_PRECISION)
    a = jnp.where(idx[:, None] > idx[None, :], a * decay, 0.0)
    # (I + A)^-1 applied to [K_b exp(gc) | V_b]: the chunk's rank-one
    # updates, undone of their dependence on each other
    rhs = jnp.concatenate([kb * jnp.exp(gc)[..., None],
                           v * beta[..., None]], axis=-1)
    sol = _solve_unit_lower(a, rhs)
    w, u = sol[..., :dk], sol[..., dk:]
    qk = jnp.einsum("...ik,...jk->...ij", q, k, precision=_PRECISION)
    qk = jnp.where(lower, qk * decay, 0.0)
    q_in = q * jnp.exp(gc)[..., None]
    k_out = k * jnp.exp(gc[..., -1:] - gc)[..., None]
    total = jnp.exp(gc[..., -1])                              # [N, B, H]

    def one(s, xs):
        w_n, u_n, qk_n, q_n, k_n, tot = xs
        v_new = u_n - jnp.einsum("bhck,bhkv->bhcv", w_n, s,
                                 precision=_PRECISION)
        o_n = (jnp.einsum("bhck,bhkv->bhcv", q_n, s, precision=_PRECISION)
               + jnp.einsum("bhij,bhjv->bhiv", qk_n, v_new,
                            precision=_PRECISION))
        s = (s * tot[..., None, None]
             + jnp.einsum("bhck,bhcv->bhkv", k_n, v_new,
                          precision=_PRECISION))
        return s, o_n

    state, o = lax.scan(one, state.astype(jnp.float32),
                        (w, u, qk, q_in, k_out, total))
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 2), 1, 3)             # [B,N,C,H,dv]
    return o.reshape(B, N * chunk, H, dv)[:, :T], state
