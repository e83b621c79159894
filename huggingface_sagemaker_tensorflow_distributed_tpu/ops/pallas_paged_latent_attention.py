"""Pallas fused paged decode kernel for LATENT attention in the absorbed
form (``models/deepseek_v2.py``): one query a slot against the pages of
ONE latent pool, walked through the block tables.

The gather path assembles a ``[slots, 1, bucket, width]`` copy of the
cache, rewrites it with the step's row and makes two passes over it
(scores and softmax statistics, the weighted sum), whatever the contexts:
17.6 ms of a 30.9 ms decode step of ``deepseek-v2-ep4-doc-sat`` at
contexts that fill a third of the 8,192 bucket (PERF.md 5, PR 34). This
kernel reads each page that holds keys once and nothing else. It is the
walk of ``ops/pallas_paged_attention.py`` (no grid, compute blocks of
several pages, one ``make_async_copy`` a page by the block table, a
block's copies in flight while the block before is attended, across slot
boundaries: :func:`~.pallas_paged_attention.page_copies`,
:func:`~.pallas_paged_attention.walk_slots`) with another body, because
a latent row is another thing than a K/V pair:

- **one pool, read once**: a row ``c | k_pe | zeros`` is a key as it is
  (all ``width`` lanes against the absorbed query ``q_nope W_uk^T | q_pe
  | zeros``) and a value in its first ``rank`` lanes, so a block is
  fetched once for both matmuls;
- **no heads axis**: a page ``[block_size, width]`` is already rows of
  keys. Every query head attends every row: no head mask, no grouping,
  no head-major case;
- **at the ridge, not under the roof**: ``[H, width] x [width, keys]``
  and ``[H, keys] x [keys, rank]`` for 128 heads are about 230 FLOP a
  byte of the block, the v5e's own ratio, where a K/V block of grouped
  heads is bound by its bytes. Measured at doc-sat's shape (32 slots of
  2,860 keys in the mean): 0.34 ms a layer-call, 38% of the MXU's peak
  and 313 GB/s of needed bytes, where the gather's four operations
  were 3.5 ms of the step; a full bucket 0.84 ms, 44%. Blocks of 1,024
  keys measured 3% and 10% faster and are not taken: the block is the
  K/V kernel's constant (my chip runs, PR 34; PERF.md 6).

Scores and softmax statistics are float32 (Dao et al. 2022's running
max / sum), operands the query's dtype (bf16 in serving; float32 operands
take ``Precision.HIGHEST``), the weights cast to it for the sum: what
``attend_absorbed`` computes with, no lower. ``W_uv`` and ``o_proj`` stay
outside. Inactive rows (``context_len == 0``) walk no page and return
ZEROS.

``tests/test_paged_latent_kernel.py`` holds the kernel to
``attend_absorbed`` over a gathered cache in interpret mode,
``tests/test_pallas_latent_attention.py`` compiles it for the v5e at the
cell's shape, ``tests/test_serve_latent.py`` holds the engine on it to
``generate_causal``; ``chipbench/tools/latent_decode_microbench.py`` times
it on the chip against the gather path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from huggingface_sagemaker_tensorflow_distributed_tpu.ops import (
    pallas_paged_attention as paged,
)


def _latent_kernel(tbl_ref, ctx_ref, q_hbm, pool, o_hbm, buf, q_buf, o_buf,
                   sem, q_sem, o_sem, *, scale, block_size, pages,
                   table_width, rank):
    """The whole call. ``tbl_ref`` (SMEM, ``[slots * table_width]``) and
    ``ctx_ref`` (SMEM, ``[slots]``) drive the DMAs. Everything else stays
    in HBM: ``pool`` ``[N, block_size, width]`` with ``buf`` ``[2, pages,
    block_size, width]`` its double buffer, and the queries ``q_hbm``
    ``[slots, H, width]`` and outputs ``o_hbm`` ``[slots, H, rank]``, a
    slot's at a time through ``q_buf`` / ``o_buf`` (``[2, H, ...]``: 128
    heads of a 640-wide query are 160 KB a slot, and 64 slots of them
    with their outputs pass the 16 MiB the v5e gives a kernel; rehearsal
    compile, PR 34), so the kernel's fast memory is the same for any
    number of slots."""
    num_slots, num_heads, _ = q_hbm.shape
    P = pages
    start, wait = paged.page_copies(tbl_ref, [pool], [buf], sem,
                                    table_width, P)

    def q_copy(s):
        return pltpu.make_async_copy(q_hbm.at[s], q_buf.at[s % 2],
                                     q_sem.at[s % 2])

    def o_copy(s):
        return pltpu.make_async_copy(o_buf.at[s % 2], o_hbm.at[s],
                                     o_sem.at[s % 2])

    def walk(s):
        ctx = ctx_ref[s]
        end = jnp.minimum(
            lax.div(ctx + (block_size - 1), jnp.int32(block_size)),
            table_width)
        return ctx, jnp.int32(0), end

    # a row no DMA has written yet is a VALUE too: its weight is an exact
    # 0, and 0 * NaN is not. Stale rows of an earlier block are finite
    buf[...] = jnp.zeros_like(buf)

    col = lax.broadcasted_iota(jnp.int32, (num_heads, P * block_size), 1)
    compute = q_hbm.dtype
    precision = (lax.Precision.HIGHEST if compute == jnp.float32
                 else lax.Precision.DEFAULT)

    def init(s):
        # this slot's query has been in flight since the slot before
        q_copy(s).wait()

        @pl.when(s + 1 < num_slots)
        def _next_query():
            q_copy(s + 1).start()

        return (jnp.full((num_heads, 1), paged._NEG_INF, jnp.float32),
                jnp.zeros((num_heads, 1), jnp.float32),
                jnp.zeros((num_heads, rank), jnp.float32))

    def attend(s, ctx, page, buf_slot, state):
        m, l, acc = state
        rows = buf[buf_slot].reshape(P * block_size, -1).astype(compute)
        scores = lax.dot_general(
            q_buf[s % 2], rows, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=precision) * scale             # [H, P * block_size]
        scores = jnp.where(col < ctx - page * block_size, scores,
                           paged._NEG_INF)
        m_new = jnp.maximum(m, jnp.max(scores, axis=1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(scores - m_new)
        l_new = alpha * l + jnp.sum(p, axis=1, keepdims=True)
        acc_new = alpha * acc + lax.dot_general(
            p.astype(compute), rows[:, :rank], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision)
        return m_new, l_new, acc_new

    def finish(s, state):
        _, l, acc = state

        # the half of ``o_buf`` this slot writes went out two slots ago
        @pl.when(s >= 2)
        def _landed():
            o_copy(s - 2).wait()

        # a context-0 (inactive) row walks no page: l == 0, output 0
        o_buf[s % 2] = (acc / jnp.where(l == 0.0, 1.0, l)).astype(
            o_buf.dtype)
        o_copy(s).start()

    q_copy(0).start()
    paged.walk_slots(num_slots, P, walk, start, wait, init, attend, finish)
    for s in range(max(num_slots - 2, 0), num_slots):
        o_copy(s).wait()


@functools.partial(jax.jit,
                   static_argnames=("rank", "scale", "interpret", "pages"))
def _latent_call(q, pool, block_tables, context_lens, rank, scale, interpret,
                 pages):
    S, H, width = q.shape
    _, bs, _ = pool.shape
    kernel = functools.partial(
        _latent_kernel, scale=scale, block_size=bs, pages=pages,
        table_width=block_tables.shape[1], rank=rank)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        kernel,
        in_specs=[smem, smem, hbm, hbm],
        out_specs=hbm,
        out_shape=jax.ShapeDtypeStruct((S, H, rank), q.dtype),
        scratch_shapes=[pltpu.VMEM((2, pages, bs, width), pool.dtype),
                        pltpu.VMEM((2, H, width), q.dtype),
                        pltpu.VMEM((2, H, rank), q.dtype),
                        pltpu.SemaphoreType.DMA((2, 1)),
                        pltpu.SemaphoreType.DMA((2,)),
                        pltpu.SemaphoreType.DMA((2,))],
        interpret=interpret,
        name="paged_latent_decode",
    )(block_tables.astype(jnp.int32).reshape(-1),
      context_lens.astype(jnp.int32), q, pool)


def paged_latent_decode_attention(q, pool, block_tables, context_lens, *,
                                  rank: int, scale: float,
                                  interpret: bool | None = None):
    """Fused single-token paged decode attention over a latent pool.

    ``q`` [slots, heads, row] is the ABSORBED query (``q_nope W_uk^T |
    q_pe`` padded with zeros to the pool's row: ``models.deepseek_v2.
    absorbed_query``); ``pool`` [num_blocks, block_size, row] holds ``c |
    k_pe | zeros`` a token, the step's own row already written;
    ``block_tables`` [slots, blocks_per_slot] (the columns of the step's
    context bucket: they bound the walk, the contexts set its length);
    ``context_lens`` [slots] counts each slot's keys, the query's own
    included, and is at most the tables' span. Returns the weighted sum
    of the rows' first ``rank`` lanes, [slots, heads, rank], in ``q``'s
    dtype (``W_uv`` comes after it); context-0 rows return zeros."""
    N, bs, row = pool.shape
    if q.shape[-1] != row:
        raise ValueError(
            f"the absorbed query's width {q.shape[-1]} is not the pool's "
            f"row width {row}")
    if not 0 < rank <= row:
        raise ValueError(f"values in the first {rank} lanes of a "
                         f"{row}-wide row")
    if interpret is None:
        interpret = jax.devices()[0].platform != "tpu"
    # no heads axis: a block of the K/V kernel's keys is 640 KB of the
    # published row in bf16, under its byte bound whatever the model
    pages = max(1, min(paged._BLOCK_KEYS // bs, N))
    return _latent_call(q, pool, block_tables, context_lens, int(rank),
                        float(scale), interpret, pages)
