"""Pallas fused paged-attention decode kernel: single-token decode
attention that walks per-slot block tables DIRECTLY, with optional
in-kernel int8 KV dequantization.

The gather path (``ops.attention.paged_attention(impl="xla")``)
materializes a dense ``[slots, H, width, D]`` view of each slot's paged
KV, rewrites it with the step's K/V, repeats it to the query heads and
attends over it: five passes over a bucket-wide copy of the cache,
whatever the contexts (PERF.md §5, PR 29: 79% of a decode step of
``qwen2.5-3b-chat-sat``). This kernel reads each page that holds keys
once, and nothing else (Kwon et al. 2023 pay a single fused read here):

- **no grid, one walk**: the kernel is one program that loops over the
  slots and, for each, over COMPUTE BLOCKS of several pages: 512 keys,
  or as many as 1 MiB of a pool holds where that is fewer (many or wide
  KV heads: :func:`block_pages`), so the kernel's fast memory does not
  grow with the model's heads. A slot's walk runs from the band's first page
  (0 without a window) to ``ceil(context / block_size)``: pages past the
  context are never fetched, a slot at context 0 costs no page, and the
  time follows the contexts, not the bucket ``width`` (which only bounds
  the block-table columns a walk may touch);
- **block-table indirection in the DMAs**: tables and context lengths
  live in SMEM, the pools stay in HBM (``memory_space=pl.ANY``) and each
  page is one ``make_async_copy`` from ``pool[tables[s, i]]`` into its
  row range of a VMEM buffer; a block's copies are issued together, and
  double-buffered against the arithmetic of the block before it, across
  slot boundaries too;
- **pages as rows**: a page ``[block_size, H_kv, D]`` is read as
  ``[block_size * H_kv, D]``, one row a (key, kv head) pair. For the
  pool's layout on the chip that view is a bitcast (rehearsal compile
  for the v5e, PR 29: bf16 ``[N, 16, 2, 128]`` is tiled ``(2, 128)``
  with the two heads packed in one 32-bit word, byte for byte what
  ``[N, 32, 128]`` tiled ``(8, 128)`` is), so the layout that prefill,
  copy-on-write, swap and the prefix index share stays as it is. Where
  the chip stores a page HEAD-major instead (:func:`head_major_rows`:
  kv head counts that are no multiple of 8, such as 30), the rows are
  taken in that order, one row a (kv head, key) pair, which is the
  bitcast there; the keys' positions then come from a small table and
  not from the column's index;
- **QKᵀ and PV on the MXU**: ``[H, D] x [D, rows]`` for all query heads
  against all rows of the block, the (query head, row) pairs whose kv
  heads differ masked out with the keys past the context, float32
  logits and softmax statistics (Dao et al. 2022's running max / sum),
  the weights cast to the compute dtype for ``[H, rows] x [rows, D]``.
  Operands are the query's dtype (bf16 in serving; float32 operands take
  ``Precision.HIGHEST``), accumulation is float32: no lower than the
  gather path, whose logits are a bf16 einsum's output. Splitting a
  block by kv head instead would relayout every byte (the heads
  interleave at sublane granularity); the masked product costs ``H_kv``
  times the MXU work, which is not what bounds a decode step;
- **sliding-window banding**: with ``window`` set the walk starts at the
  page holding position ``context - window`` and in-band keys mask per
  position (key kept iff ``0 <= q_pos - k_pos < window``);
- **in-tile int8 dequant**: with scale pools given, K/V pages load as
  int8 and the fp32 per-(position, head) scales are applied where they
  are one multiply a logit: ``q . (k s) = (q . k) s`` scales a COLUMN of
  the logits, ``sum p (v s) = sum (p s) v`` a column of the weights. The
  scales themselves (4 bytes a row against the row's ``D``) are
  gathered by the block tables outside the kernel into ``[slots, 1,
  width * H_kv]`` row vectors: a scale pool's minor dimension is 1, and
  a page of it cannot be sliced for a DMA.
- **head sizes off the lane width**: a pool whose ``D`` is no multiple
  of 128 is padded to one before the call, a copy of the pool a call.
  Such a pool is not page-contiguous on the chip to begin with (the
  v5e's compiler lays bf16 ``[N, 16, 12, 64]`` out block-minor:
  rehearsal compile, PR 29), so the result is right and not fast, and
  the serving engine's own choice never takes the kernel there.

The walk itself (:func:`page_copies`, :func:`walk_slots`) knows nothing
of K/V: ``ops/pallas_paged_latent_attention.py`` runs it over ONE latent
pool with a body of its own (PR 34).

Inactive rows (``context_len == 0``) return ZEROS (the XLA path returns
a softmax over fully-masked junk instead — callers discard those rows
either way).

Correctness is testable without TPU hardware via
``pallas_call(interpret=True)`` — ``tests/test_paged_kernel.py`` pins
kernel-vs-XLA parity across width buckets, GQA groupings, int8/fp
pools, sliding-window bands and compute-block boundaries, and
``tests/test_serve.py`` pins engine-level token-exactness vs
``generate_causal`` with the kernel engaged. On the chip:
``benchmarks/tpu_kernel_parity.py`` (against float64) and
``tools/paged_decode_microbench.py`` (time against the gather path).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30
# keys one compute block holds: a few hundred, so that a layer-call of
# some thousands of resident tokens is tens of blocks (16, 32 and 64
# pages of 16 keys timed on the chip at chat-sat's shape, PR 29: 32)
_BLOCK_KEYS = 512
# ... and the bytes of one pool it may hold. Everything the kernel keeps
# in VMEM is a multiple of this (four such buffers, the loaded block,
# query heads x rows of float32 logits and bias), whatever ``H_kv``, so
# 32 KV heads compile where two do (the v5e gives a kernel 16 MiB)
_BLOCK_BYTES = 1 << 20


def block_pages(block_size: int, kv_heads: int, head_dim: int, itemsize: int,
                pool_blocks: int, lane_rows: bool = False) -> int:
    """Pages of one compute block: ``_BLOCK_KEYS`` keys' worth, no more
    than ``_BLOCK_BYTES`` of a pool and no more than the pool has. With
    ``lane_rows`` (int8 pools on the chip: a block's scales are sliced
    along lanes at its first row) whole lane tiles of rows only."""
    rows = block_size * kv_heads
    pages = max(1, min(_BLOCK_KEYS // block_size,
                       _BLOCK_BYTES // (rows * head_dim * itemsize),
                       pool_blocks))
    if lane_rows:
        align = 128 // math.gcd(rows, 128)
        if pages < align:
            raise ValueError(
                f"int8 pools: a compute block of {pages} pages x {rows} "
                f"(key, head) rows is no multiple of 128 rows, and {align} "
                f"pages do not fit the pool ({pool_blocks} blocks) or "
                f"{_BLOCK_BYTES} bytes")
        pages -= pages % align
    return pages


def head_major_rows(kv_heads: int) -> bool:
    """True where the v5e stores a page of ``[block_size, kv_heads, D]``
    with its HEADS major: ``[N, 16, H, 128]`` has the default layout
    ``{3,1,2,0}`` for ``H`` = 12 or 30, bf16 or float32, and ``{3,2,1,0}``
    for 2, 4, 8, 16, 24, 32, 40 (rehearsal compiles for the v5e, PR 33:
    the compiler moves a second-minor dimension that is no multiple of 8
    sublanes out of the tile, and tiles ``block_size x D``). Read as
    key-major rows such a pool is copied whole for every call (eight
    437 MB copies in a decode step of Olmo-Hybrid's four full layers);
    read as head-major rows it is a bitcast. A pure function of the
    shape, the same on every backend: an interpreted call on a CPU runs
    the form the chip would."""
    return kv_heads % 8 != 0 and kv_heads not in (1, 2, 4)


def page_copies(tbl_ref, pools, bufs, sem, table_width: int, P: int):
    """``(start, wait)`` of a compute block's page copies: from each of
    ``pools`` (HBM, ``[N, rows, D]``) by the block table ``tbl_ref``
    (SMEM, ``[slots * table_width]``) into ``bufs`` (VMEM, ``[2, P, rows,
    D]`` each), signalled on ``sem`` (``[2, len(pools)]``). Both take
    ``(slot, first table column, pages that hold keys <= P, buffer
    half)``."""

    def copies(s, page, buf_slot, p):
        """Page ``p`` of a block starting at table column ``page``."""
        block = tbl_ref[s * table_width + page + p]
        return [pltpu.make_async_copy(pool.at[block], buf.at[buf_slot, p],
                                      sem.at[buf_slot, i])
                for i, (pool, buf) in enumerate(zip(pools, bufs))]

    def each_page(n, fn):
        def body(p, carry):
            fn(p)
            return carry
        lax.fori_loop(0, n, body, 0)

    def start(s, page, n, buf_slot):
        """Issue the DMAs of the ``n <= P`` pages of a block that hold
        keys: unrolled where the block is full (a fifth faster at long
        contexts than the loop: chip run, PR 29)."""
        def go(p):
            for c in copies(s, page, buf_slot, p):
                c.start()

        @pl.when(n == P)
        def _full():
            for p in range(P):
                go(p)

        @pl.when(n < P)
        def _ragged():
            each_page(n, go)

    def wait(s, page, n, buf_slot):
        """A DMA semaphore counts bytes: a full block's pages are ONE
        wait for the whole buffer's worth (a tenth of a call's time
        against a wait a page: chip run, PR 29)."""
        @pl.when(n == P)
        def _full():
            for i, (pool, buf) in enumerate(zip(pools, bufs)):
                pltpu.make_async_copy(pool.at[pl.ds(0, P)], buf.at[buf_slot],
                                      sem.at[buf_slot, i]).wait()

        @pl.when(n < P)
        def _ragged():
            each_page(n, lambda p: [c.wait()
                                    for c in copies(s, page, buf_slot, p)])

    return start, wait


def walk_slots(num_slots: int, P: int, walk, start, wait, init, attend,
               finish):
    """Every slot's walk over its compute blocks, the next block's pages
    in flight while one is attended, across slot boundaries too.
    ``walk(s)`` gives slot ``s``'s ``(context, first page, end page)``;
    ``start`` / ``wait`` are :func:`page_copies`'; a slot's running state
    starts as ``init(s)``, ``attend(s, ctx, page, buf_slot, state)`` folds
    the landed block at table column ``page`` into it, and ``finish(s,
    state)`` writes the slot's output (a slot that walks no page
    finishes on ``init(s)``)."""

    def slot_body(s, carry):
        done, prefetched = carry     # blocks so far; is my first in flight
        ctx, first, end = walk(s)
        nblk = lax.div(end - first + (P - 1), jnp.int32(P))
        nxt = jnp.minimum(s + 1, num_slots - 1)
        _, first_n, end_n = walk(nxt)
        next_walks = jnp.logical_and(s + 1 < num_slots, end_n > first_n)

        @pl.when(jnp.logical_and(nblk > 0, prefetched == 0))
        def _first():
            start(s, first, jnp.minimum(P, end - first), done % 2)

        def block_body(b, state):
            buf_slot = (done + b) % 2
            page = first + b * P
            n = jnp.minimum(P, end - page)

            # the next block's pages fly while this one is attended: this
            # slot's next block, or the next slot's first
            @pl.when(b + 1 < nblk)
            def _ahead():
                start(s, page + P, jnp.minimum(P, end - page - P),
                      1 - buf_slot)

            @pl.when(jnp.logical_and(b + 1 == nblk, next_walks))
            def _ahead_slot():
                start(nxt, first_n, jnp.minimum(P, end_n - first_n),
                      1 - buf_slot)

            wait(s, page, n, buf_slot)
            return attend(s, ctx, page, buf_slot, state)

        finish(s, lax.fori_loop(0, nblk, block_body, init(s)))
        return (done + nblk,
                jnp.logical_and(nblk > 0, next_walks).astype(jnp.int32))

    lax.fori_loop(0, num_slots, slot_body, (jnp.int32(0), jnp.int32(0)))


def _paged_kernel(tbl_ref, ctx_ref, q_ref, head_bias_ref, *refs, scale,
                  block_size, kv_heads, pages, table_width, window, int8,
                  head_major=False):
    """The whole call: every slot's walk over its pages.

    ``tbl_ref`` (SMEM, ``[slots * table_width]``) and ``ctx_ref`` (SMEM,
    ``[slots]``) drive the DMAs; ``q_ref`` / ``o_ref`` are ``[slots, H,
    D]`` in VMEM; ``head_bias_ref`` ``[H, pages * rows]`` is 0 where a
    block's row belongs to the query head's kv head and -1e30 elsewhere.
    The pools (``[N, rows, D]``, rows = block_size * kv_heads) stay in
    HBM; ``bufs`` are their double buffers ``[2, pages, rows, D]``. With
    ``int8`` two more VMEM inputs follow the bias: the gathered K and V
    scales, ``[slots, 1, (table_width + pages) * rows]``. With
    ``head_major`` (never with ``int8``) one VMEM input follows the bias
    instead: ``[1, pages * rows]`` int32, each row's key offset within a
    block."""
    key_off_ref, refs = (refs[0], refs[1:]) if head_major else (None, refs)
    scales, refs = (refs[:2], refs[2:]) if int8 else ((), refs)
    pools, o_ref, bufs, sem = refs[:2], refs[2], refs[3:5], refs[5]
    num_slots, num_heads, _ = q_ref.shape
    rows = block_size * kv_heads
    P = pages
    # a scale vector is sliced along lanes at a block's first row: keep
    # that a multiple of the lane width by starting a banded walk on a
    # page that is one
    align = 128 // math.gcd(rows, 128) if int8 else 1
    start, wait = page_copies(tbl_ref, pools, bufs, sem, table_width, P)

    def walk(s):
        """(context, first page, end page) of slot ``s``."""
        ctx = ctx_ref[s]
        end = jnp.minimum(
            lax.div(ctx + (block_size - 1), jnp.int32(block_size)),
            table_width)
        if window is None:
            return ctx, jnp.int32(0), end
        first = lax.div(jnp.maximum(ctx - window, 0),
                        jnp.int32(block_size * align)) * align
        return ctx, first, end

    # a value row no DMA has written yet must not hold a NaN: its weight
    # is an exact 0, and 0 * NaN is not. Stale rows of an earlier block
    # are finite (real K/V); the keys' junk goes through a select
    bufs[1][...] = jnp.zeros_like(bufs[1])

    col = lax.broadcasted_iota(jnp.int32, (num_heads, P * rows), 1)
    compute = q_ref.dtype
    precision = (lax.Precision.HIGHEST if compute == jnp.float32
                 else lax.Precision.DEFAULT)

    def load(i, buf_slot):
        """Stream ``i`` (0 keys, 1 values) of a landed block as
        ``[P * rows, D]`` in the compute dtype (int8 values are exact
        in it; their scales go on the logits' side)."""
        return bufs[i][buf_slot].reshape(P * rows, -1).astype(compute)

    def scale_row(i, s, page):
        """The ``[1, P * rows]`` scales of the block at ``page``."""
        return scales[i][s, :, pl.ds(pl.multiple_of(page * rows, 128),
                                     P * rows)]

    def init(s):
        return (jnp.full((num_heads, 1), _NEG_INF, jnp.float32),
                jnp.zeros((num_heads, 1), jnp.float32),
                jnp.zeros(o_ref.shape[1:], jnp.float32))

    def attend(s, ctx, page, buf_slot, state):
        m, l, acc = state
        k = load(0, buf_slot)
        logits = lax.dot_general(
            q_ref[s], k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=precision) * scale          # [H, P * rows]
        if int8:
            logits = logits * scale_row(0, s, page)
        # row c of the block is key (page * block_size + c // kv_heads)
        # of kv head c % kv_heads: the head through the bias, the
        # position without a division
        base = page * block_size
        if head_major:
            # rows run (page, kv head, key): the key's offset is read
            off = key_off_ref[...]
            keep = off < ctx - base
            if window is not None:
                keep = jnp.logical_and(keep, off >= ctx - window - base)
        else:
            keep = col < (ctx - base) * kv_heads
            if window is not None:
                keep = jnp.logical_and(
                    keep, col >= (ctx - window - base) * kv_heads)
        logits = jnp.where(keep, logits + head_bias_ref[...], _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(logits, axis=1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(logits - m_new)
        l_new = alpha * l + jnp.sum(p, axis=1, keepdims=True)
        if int8:
            # a scale past the context may be junk: 0 * NaN again
            p = jnp.where(keep, p * scale_row(1, s, page), 0.0)
        acc_new = alpha * acc + lax.dot_general(
            p.astype(compute), load(1, buf_slot),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=precision)
        return m_new, l_new, acc_new

    def finish(s, state):
        _, l, acc = state
        # a context-0 (inactive) row walks no page: l == 0, output 0
        o_ref[s] = (acc / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)

    walk_slots(num_slots, P, walk, start, wait, init, attend, finish)


@functools.partial(
    jax.jit,
    static_argnames=("scale", "window", "interpret", "pages"))
def _paged_call(q, k_pool, v_pool, block_tables, context_lens,
                k_scale_pool, v_scale_pool, scale, window, interpret,
                pages):
    S, Hq, head_dim = q.shape
    N, bs, Hkv, _ = k_pool.shape
    rows = bs * Hkv
    nb = block_tables.shape[1]
    int8 = k_scale_pool is not None
    lane_pad = 0 if interpret else -head_dim % 128
    if lane_pad:
        # zeros add nothing to a dot product; the output's are cut off
        q = jnp.pad(q, ((0, 0), (0, 0), (0, lane_pad)))
        k_pool, v_pool = (jnp.pad(p, ((0, 0),) * 3 + ((0, lane_pad),))
                          for p in (k_pool, v_pool))
    D = head_dim + lane_pad
    head_major = head_major_rows(Hkv) and not int8
    if head_major:
        # pages as rows, one row a (kv head, key) pair: the bitcast where
        # the chip stores a page head-major
        pools = [p.transpose(0, 2, 1, 3).reshape(N, rows, D)
                 for p in (k_pool, v_pool)]
    else:
        # pages as rows: one row a (key, kv head) pair (a bitcast on the
        # chip)
        pools = [k_pool.reshape(N, rows, D), v_pool.reshape(N, rows, D)]
    # the scales of each slot's table span as one row vector, with a
    # block of zeros behind it for the last block's slice to end in
    scales = [jnp.pad(sp[block_tables].reshape(S, 1, nb * rows),
                      ((0, 0), (0, 0), (0, pages * rows)))
              for sp in ((k_scale_pool, v_scale_pool) if int8 else ())]
    # query head j attends kv head j // G; row c of a block holds kv head
    # c % Hkv (head-major: row c of a page holds kv head c // bs)
    c = jnp.arange(pages * rows)
    row_head = (c % rows) // bs if head_major else c % Hkv
    head_bias = jnp.where(
        (jnp.arange(Hq) // (Hq // Hkv))[:, None] == row_head[None, :],
        0.0, _NEG_INF).astype(jnp.float32)
    key_off = ([((c // rows) * bs + c % bs).astype(jnp.int32)[None, :]]
               if head_major else [])

    kernel = functools.partial(
        _paged_kernel, scale=scale, block_size=bs, kv_heads=Hkv,
        pages=pages, table_width=nb, window=window, int8=int8,
        **({"head_major": True} if head_major else {}))
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    out = pl.pallas_call(
        kernel,
        in_specs=([smem, smem, vmem, vmem]
                  + [vmem] * (len(key_off) + len(scales)) + [hbm] * 2),
        out_specs=vmem,
        out_shape=jax.ShapeDtypeStruct((S, Hq, D), q.dtype),
        scratch_shapes=(
            [pltpu.VMEM((2, pages, rows, D), p.dtype) for p in pools]
            + [pltpu.SemaphoreType.DMA((2, 2))]),
        interpret=interpret,
        name="paged_decode",
    )(block_tables.astype(jnp.int32).reshape(-1),
      context_lens.astype(jnp.int32), q, head_bias, *key_off, *scales,
      *pools)
    return out[..., :head_dim] if lane_pad else out


def paged_decode_attention(q, k_pool, v_pool, block_tables, context_lens,
                           scale=None, width: int | None = None,
                           window: int | None = None,
                           k_scale_pool=None, v_scale_pool=None,
                           interpret: bool | None = None):
    """Fused single-token paged decode attention.

    ``q`` [slots, heads, head_dim] (one query per slot — the decode
    step's newest token, already resident in the pools);
    ``k_pool``/``v_pool`` [num_blocks, block_size, kv_heads, head_dim]
    (fp, or int8 with ``k_scale_pool``/``v_scale_pool``
    [num_blocks, block_size, kv_heads, 1] fp32 — the per-(position,
    head) scales ``models.llama.kv_quantize`` writes);
    ``block_tables`` [slots, blocks_per_slot]; ``context_lens`` [slots]
    counts valid tokens per slot (the query's own K/V included — the
    query position is ``context_lens - 1``). ``width`` (static, block
    multiple) restricts the walk to a context bucket exactly like
    :func:`~.attention.gather_paged_kv`: callers guarantee
    ``context_lens <= width``, and the time follows the contexts, not
    the bucket. ``window`` applies Mistral's sliding band (key kept iff
    ``0 <= q_pos - k_pos < window``) with the pages below the band not
    read. GQA is native: query heads must be a multiple of pool kv
    heads. Returns [slots, heads, head_dim]; context-0 rows return
    zeros."""
    if (k_scale_pool is None) != (v_scale_pool is None):
        raise ValueError("int8 pools need BOTH k_scale_pool and "
                         "v_scale_pool (or neither)")
    if q.shape[1] % k_pool.shape[2]:
        raise ValueError(
            f"query heads {q.shape[1]} must be a multiple of pool kv "
            f"heads {k_pool.shape[2]} (GQA grouping)")
    bs = k_pool.shape[1]
    if width is not None:
        if width % bs:
            raise ValueError(f"bucket width {width} must be a multiple "
                             f"of block_size {bs}")
        nb = width // bs
        if nb > block_tables.shape[1]:
            raise ValueError(
                f"bucket width {width} needs {nb} blocks/slot but the "
                f"block table holds {block_tables.shape[1]}")
        block_tables = block_tables[:, :nb]
    head_dim = q.shape[-1]
    scale = scale if scale is not None else head_dim ** -0.5
    if interpret is None:
        interpret = jax.devices()[0].platform != "tpu"
    # how many pages are fetched together and attended as one matmul (a
    # full block's one wait is described by the pool's first pages)
    pages = block_pages(bs, k_pool.shape[2], head_dim + -head_dim % 128,
                        k_pool.dtype.itemsize, k_pool.shape[0],
                        lane_rows=k_scale_pool is not None and not interpret)
    return _paged_call(q, k_pool, v_pool, block_tables, context_lens,
                       k_scale_pool, v_scale_pool, float(scale),
                       window, interpret, pages)
