"""Pallas fused latent (MLA) attention for a CHUNK of queries: the
expanded form of ``models/deepseek_v2.py`` with a key block's scores
kept on the chip.

The XLA form (``models/deepseek_v2.py::attend_expanded``) walks the keys
a block at a time under a running softmax, and each block's ``[rows,
heads, queries, keys]`` float32 scores go to HBM and back five times
(537 MB a block a layer at doc-sat's shape: 69% of that cell's prefill
time, PERF.md 5, PR 31's ledger line). Here one grid step is a (row,
head, query block, key block):

- **expansion in the step**: the block's latent rows ``c`` ``[keys,
  rank]`` times that head's slice of ``W_kvb`` ``[rank, nope + v]`` give
  the head's ``k_nope | v`` in VMEM, rounded to the cache's type as the
  XLA einsum's output is. Each (row, head, key block) is expanded once a
  query block: the FLOPs the XLA form spends, and no ``k | v`` of the
  bucket in HBM;
- **scores, softmax and values in VMEM**: ``score = (q_nope . k_nope +
  q_pe . k_pe) * scale`` in float32 as two MXU products (the rotary one
  against the row's own tail ``k_pe | zeros``, the query's rotary part
  padded with zeros to that width: a lane-aligned slice, and exact),
  float32 running maximum, sum and accumulator in scratch that lives
  across the key blocks of one (row, head, query block), probabilities
  cast to the cache's type for the value product. Nothing of shape
  ``[rows, heads, queries, keys]`` exists anywhere;
- **the mask from what the caller has**: key ``j`` is seen by query
  ``s`` of row ``b`` iff ``j <= start[b] + s`` and ``key_valid[b, j]``.
  ``start`` is scalar-prefetched, the comparison is two iotas (made
  only for a block on the diagonal: one wholly behind the block's first
  query is masked by ``key_valid`` alone), and ``key_valid`` rides as a
  ``[rows, 1, keys]`` block; no ``[rows, queries, keys]`` bias is built;
- **the skip, a row at a time**: a step whose key block lies wholly
  past ``start[b] + (last query of the block)`` does nothing, and its
  ``index_map`` names the row's last needed block again, so nothing is
  fetched for it: a row costs its own context, whatever the bucket and
  whatever the other rows of the dispatch hold. (The XLA form runs a
  block when ANY row sees it.)

Operands are the cache's type (bf16 in serving; float32 operands take
``Precision.HIGHEST`` on the chip), accumulation is float32: the XLA
form's precision, nothing lower. The output is written lane-dense as
``[rows, queries, heads * v]``: the reshape to ``[rows, queries, heads,
v]`` is free and ``o_proj`` needs no transpose.

On the chip the blocks must tile: ``rank``, ``nope``, ``v`` and the
row's tail whole multiples of 128 lanes, queries and keys whole
multiples of the block (:func:`takes`). Interpret mode (off a TPU)
takes any shape: ``tests/test_pallas_latent_attention.py`` holds it to
the XLA form there, ``benchmarks/tpu_kernel_parity.py`` to float64 on
the chip, and ``chipbench/tools/latent_prefill_microbench.py`` times it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e9          # the additive mask of the XLA form
_LANES = 128
# queries a pass of a step attends (the block's keys stay expanded in
# VMEM for all passes). 128, 256 and 512 timed on the chip at doc-sat's
# shapes (chipbench/tools/latent_prefill_microbench.py --q-rows, PR 32):
# 16.6, 16.2 and 17.1 ms a four-row layer-call at start 4,096 (call
# p32a, the queries' re-layout outside the timed loop)
_Q_ROWS = 256


def takes(*, q_len: int, width: int, block: int, rank: int, nope: int,
          v_dim: int, row: int, dtype) -> bool:
    """Whether the compiled kernel has blocks for a call: ``q_len``
    queries and ``width`` keys whole multiples of ``block``, the
    latent's ``rank``, the heads' ``nope`` and ``v_dim`` and the row's
    tail (``row - rank``: ``k_pe | zeros``) whole lane tiles, a floating
    type the MXU takes."""
    return (q_len % block == 0 and width % block == 0
            and all(n > 0 and n % _LANES == 0
                    for n in (rank, nope, v_dim, row - rank))
            and jnp.dtype(dtype) in (jnp.dtype(jnp.bfloat16),
                                     jnp.dtype(jnp.float32)))


def _kernel(start_ref, q_ref, lat_ref, w_ref, valid_ref, o_ref, *rest,
            scale, rank, nope, block, q_rows, count_steps):
    """Grid (rows, heads, query blocks, key blocks), key blocks
    innermost: the running softmax in scratch carries across them."""
    steps_ref, (kv_ref, acc_ref, m_ref, l_ref) = (
        (rest[0], rest[1:]) if count_steps else (None, rest))
    b, iq, ik = pl.program_id(0), pl.program_id(2), pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        if steps_ref is not None:
            steps_ref[...] = jnp.zeros_like(steps_ref)

    q_first = start_ref[b] + iq * block     # the block's first query
    k_first = ik * block
    dtype = lat_ref.dtype
    precision = (lax.Precision.HIGHEST if dtype == jnp.float32
                 else lax.Precision.DEFAULT)
    dot = functools.partial(lax.dot_general,
                            preferred_element_type=jnp.float32,
                            precision=precision)
    inner, outer = (((1,), (0,)), ((), ())), (((1,), (1,)), ((), ()))

    def attend(diagonal: bool):
        """The block's keys, expanded once, against the query block
        ``q_rows`` queries a pass. ``diagonal``: some query precedes
        some key of the block, so the causal comparison is needed; below
        the diagonal ``key_valid`` alone masks."""
        kv_ref[...] = dot(lat_ref[0, :, :rank], w_ref[...],
                          inner).astype(dtype)
        valid = valid_ref[0] != 0                          # [1, keys]
        bias = jnp.where(valid, 0.0, NEG_INF)
        for r in range(0, block, q_rows):
            rows = pl.ds(r, q_rows)
            s = (dot(q_ref[0, 0, rows, :nope], kv_ref[:, :nope], outer)
                 + dot(q_ref[0, 0, rows, nope:], lat_ref[0, :, rank:],
                       outer)) * scale
            if diagonal:
                k_pos = k_first + lax.broadcasted_iota(
                    jnp.int32, (q_rows, block), 1)
                q_pos = q_first + r + lax.broadcasted_iota(
                    jnp.int32, (q_rows, block), 0)
                s = s + jnp.where(
                    jnp.logical_and(k_pos <= q_pos, valid), 0.0, NEG_INF)
            else:
                s = s + bias
            m_prev, l_prev = m_ref[rows, :1], l_ref[rows, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            keep_old = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            l_new = l_prev * keep_old + jnp.sum(p, axis=-1, keepdims=True)
            m_ref[rows, :] = jnp.broadcast_to(m_new, (q_rows, _LANES))
            l_ref[rows, :] = jnp.broadcast_to(l_new, (q_rows, _LANES))
            acc_ref[rows, :] = (acc_ref[rows, :] * keep_old
                                + dot(p.astype(dtype), kv_ref[:, nope:],
                                      inner))
        if steps_ref is not None:
            steps_ref[...] += 1

    # a key block past the block's last query holds no key it may see,
    # and one wholly behind its first query none it may not
    below = k_first + block - 1 <= q_first
    pl.when(below)(lambda: attend(False))
    pl.when(jnp.logical_and(jnp.logical_not(below),
                            k_first <= q_first + block - 1))(
        lambda: attend(True))

    @pl.when(ik == pl.num_programs(3) - 1)
    def _finish():
        # key block 0 ran: l > 0 on every row
        o_ref[0] = (acc_ref[...] / l_ref[:, :1]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "rank", "scale", "block", "q_rows", "interpret", "count_steps"))
def _call(q, latent, w, start, valid, rank, scale, block, q_rows, interpret,
          count_steps):
    B, H, S, q_dim = q.shape
    W, row = latent.shape[1:]
    kv_dim = w.shape[1] // H
    nope = q_dim - (row - rank)
    v_dim = kv_dim - nope
    nq, nk = S // block, W // block

    def key_block(b, iq, ik, start_ref):
        """The block a step reads: its own, or the last one the query
        block needs once it is past that (fetched already: no DMA)."""
        last = lax.div(start_ref[b] + (iq + 1) * block - 1, jnp.int32(block))
        return jnp.minimum(ik, last)

    in_specs = [
        pl.BlockSpec((1, 1, block, q_dim),
                     lambda b, h, iq, ik, st: (b, h, iq, 0)),
        pl.BlockSpec((1, block, row),
                     lambda b, h, iq, ik, st: (b, key_block(b, iq, ik, st), 0)),
        pl.BlockSpec((rank, kv_dim), lambda b, h, iq, ik, st: (0, h)),
        pl.BlockSpec((1, 1, block),
                     lambda b, h, iq, ik, st: (b, 0, key_block(b, iq, ik, st))),
    ]
    out_specs = [pl.BlockSpec((1, block, v_dim),
                              lambda b, h, iq, ik, st: (b, iq, h))]
    out_shape = [jax.ShapeDtypeStruct((B, S, H * v_dim), latent.dtype)]
    if count_steps:
        # the key blocks that ran for a (row, head, query block), in
        # every element of its tile (tests only)
        out_specs.append(pl.BlockSpec(
            (1, 1, 8, _LANES), lambda b, h, iq, ik, st: (b, h * nq + iq,
                                                          0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((B, H * nq, 8, _LANES),
                                              jnp.int32))
    outs = pl.pallas_call(
        functools.partial(_kernel, scale=scale, rank=rank, nope=nope,
                          block=block, q_rows=q_rows,
                          count_steps=count_steps),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(B, H, nq, nk),
            in_specs=in_specs, out_specs=out_specs,
            scratch_shapes=[
                pltpu.VMEM((block, kv_dim), latent.dtype),    # k_nope | v
                pltpu.VMEM((block, v_dim), jnp.float32),      # accumulator
                pltpu.VMEM((block, _LANES), jnp.float32),     # running max
                pltpu.VMEM((block, _LANES), jnp.float32),     # running sum
            ]),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="latent_prefill",
    )(start, q, latent, w, valid)
    if count_steps:
        return outs[0], outs[1][:, :, 0, 0].reshape(B, H, nq)
    return outs[0]


def latent_prefill_attention(q_nope, q_pe, latent, w_kvb, start=None,
                             key_valid=None, *, rank: int, scale: float,
                             block: int = 512, interpret: bool | None = None,
                             count_steps: bool = False):
    """Fused expanded latent attention of a chunk of queries.

    ``q_nope`` [B, S, H, nope], ``q_pe`` [B, S, H, rope] (rotated),
    ``latent`` [B, W, row] (``c | k_pe | zeros``, ``row > rank``),
    ``w_kvb`` [rank, H, nope + v]: the arguments of
    ``models/deepseek_v2.py::attend_expanded``, and in place of its
    ``[B, S, W]`` bias what that bias is made from: ``start`` [B] int32
    (query ``s`` of row ``b`` sits at position ``start[b] + s``; None:
    0, the plain forward) and ``key_valid`` [B, W] bool (None: every
    key). Key ``j`` is seen iff ``j <= start[b] + s`` and
    ``key_valid[b, j]``; callers guarantee ``start + S <= W``. ``block``
    is both the query block and the key block (``S`` and ``W`` whole
    multiples of it). Returns [B, S, H, v] in the latent's type; with
    ``count_steps`` also ``[B, H, S // block]`` int32, the key blocks
    that ran."""
    B, S, H, nope = q_nope.shape
    W, row = latent.shape[1:]
    if S % block or W % block:
        raise ValueError(
            f"{S} queries and {W} keys are not whole multiples of the "
            f"kernel's block {block}: attend this call by the XLA form")
    if interpret is None:
        interpret = jax.devices()[0].platform != "tpu"
    # head-major queries, the rotary part padded to the row's tail: its
    # zeros meet the row's zeros
    q = jnp.concatenate([q_nope, q_pe], axis=-1).astype(latent.dtype)
    q = jnp.pad(q.transpose(0, 2, 1, 3),
                [(0, 0)] * 3 + [(0, row - rank - q_pe.shape[-1])])
    start = (jnp.zeros((B,), jnp.int32) if start is None
             else start.astype(jnp.int32))
    valid = (jnp.ones((B, 1, W), jnp.int32) if key_valid is None
             else key_valid.astype(jnp.int32)[:, None, :])
    out = _call(q, latent, w_kvb.reshape(rank, -1).astype(latent.dtype),
                start, valid, rank, float(scale), block, min(block, _Q_ROWS),
                interpret, count_steps)
    if count_steps:
        return out[0].reshape(B, S, H, -1), out[1]
    return out.reshape(B, S, H, -1)
