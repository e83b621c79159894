"""Attention kernels.

TPU-native replacement for the attention compute the reference gets from
TF/CUDA kernels inside ``TFAutoModelForSequenceClassification``
(reference ``scripts/train.py:117``). Three tiers, selected at trace
time:

1. ``xla`` — einsum + softmax, fully fused by XLA; correct everywhere
   (CPU tests, TPU). The default.
2. ``flash`` — Pallas blockwise flash attention (``ops/pallas_attention.py``)
   for long sequences on TPU, O(seq) memory.
3. ``ring`` — sequence-parallel ring attention over the ``seq`` mesh axis
   (``parallel/ring_attention.py``) for sequences longer than one chip's
   memory.

All tiers take [batch, heads, q_len, head_dim] q and [batch, heads,
kv_len, head_dim] k/v plus an additive float mask broadcastable to
[batch, heads, q_len, kv_len], and return [batch, heads, q_len, head_dim].
Softmax is computed in float32 regardless of input dtype (bf16-safe,
SURVEY.md §7 hard-part 5).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def xla_attention(q, k, v, mask=None, scale=None):
    """Reference einsum attention; XLA fuses mask+softmax into the matmuls."""
    head_dim = q.shape[-1]
    scale = scale if scale is not None else head_dim ** -0.5
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    logits = logits.astype(jnp.float32)
    if mask is not None:
        logits = logits + mask.astype(jnp.float32)
    weights = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", weights, v)


def dot_product_attention(q, k, v, mask=None, scale=None, impl: str = "xla",
                          causal: bool = False, window: int | None = None):
    """Dispatch on implementation tier. ``impl='flash'`` requires TPU;
    ``impl='ring'`` requires an ambient mesh with a ``seq`` axis
    (``parallel.mesh.use_mesh`` / Trainer sets it). ``causal`` applies
    autoregressive masking in whichever tier is fastest for it (the
    flash kernel skips above-diagonal tiles entirely). ``window``
    (requires ``causal``) restricts each query to the last N positions
    — Mistral's sliding window; the flash kernel also skips tiles
    entirely BELOW the band, so long-sequence banded attention costs
    O(S·window) instead of O(S²)."""
    if window is not None and not causal:
        raise ValueError("window requires causal=True (sliding-window "
                         "attention is an autoregressive construct)")
    if impl == "flash":
        from huggingface_sagemaker_tensorflow_distributed_tpu.ops.pallas_attention import (
            flash_attention,
        )
        return flash_attention(q, k, v, mask=mask, scale=scale,
                               causal=causal, window=window)
    if impl == "ring":
        if window is not None:
            # ring attention shards the seq axis; banding it needs
            # window-aware ring scheduling — not implemented
            raise ValueError("sliding window is not supported with "
                             "impl='ring'")
        from huggingface_sagemaker_tensorflow_distributed_tpu.parallel.ring_attention import (
            ring_attention_or_fallback,
        )
        return ring_attention_or_fallback(q, k, v, mask=mask, scale=scale,
                                          causal=causal)
    if window is not None:
        band = make_banded_causal_mask(q.shape[2], window, k.shape[2])
        mask = band if mask is None else mask + band
        causal = False                        # the band includes causality
    if impl != "xla":
        raise ValueError(f"unknown attention impl {impl!r} (xla | flash | ring)")
    if causal:
        cm = make_causal_mask(q.shape[2], k.shape[2])
        mask = cm if mask is None else mask + cm
    return xla_attention(q, k, v, mask=mask, scale=scale)


def make_attention_mask(attention_mask, dtype=jnp.float32, neg=-1e9,
                        segment_ids=None):
    """[batch, kv_len] {0,1} padding mask → additive [batch, 1, 1, kv_len].

    The reference feeds HF models a {0,1} ``attention_mask`` built by the
    tokenizer (``scripts/train.py:75-83``); this converts that contract to
    the additive-logit form the kernels use.

    With ``segment_ids`` (token-packed batches, ``data.pipeline.
    pack_examples``) the result is instead the block-diagonal
    [batch, 1, q_len, kv_len] segment mask — packed examples must not
    attend across segment boundaries.
    """
    if segment_ids is not None:
        return make_segment_mask(segment_ids, dtype=dtype, neg=neg)
    m = attention_mask[:, None, None, :].astype(dtype)
    return (1.0 - m) * neg


def make_segment_mask(segment_ids, dtype=jnp.float32, neg=-1e9):
    """[batch, len] int segment ids (1-based per packed example, 0 on
    padding) → additive [batch, 1, q_len, kv_len] mask that keeps a
    (query, key) pair iff both tokens belong to the SAME nonzero
    segment — the cross-contamination guard of packed batching (Krell
    et al., 2021, "Efficient Sequence Packing without
    Cross-contamination"). Composes additively with the causal/banded
    masks; padding queries attend nothing, which the ``neg``-additive
    (not -inf) convention keeps NaN-free through softmax."""
    seg_q = segment_ids[:, None, :, None]
    seg_k = segment_ids[:, None, None, :]
    keep = (seg_q == seg_k) & (seg_k > 0)
    return jnp.where(keep, 0.0, neg).astype(dtype)


def make_causal_mask(q_len: int, kv_len: int | None = None, dtype=jnp.float32, neg=-1e9):
    kv_len = kv_len or q_len
    i = jnp.arange(q_len)[:, None]
    j = jnp.arange(kv_len)[None, :]
    return jnp.where(j <= i, 0.0, neg).astype(dtype)[None, None, :, :]


def make_banded_causal_mask(q_len: int, window: int,
                            kv_len: int | None = None, dtype=jnp.float32,
                            neg=-1e9):
    """Causal + sliding window: key allowed iff 0 <= q - k < window
    (Mistral semantics) — THE band definition; every banded path
    (dispatch fallback, flash fallback, model-level masks) uses this."""
    kv_len = kv_len or q_len
    i = jnp.arange(q_len)[:, None]
    j = jnp.arange(kv_len)[None, :]
    keep = (j <= i) & (j > i - window)
    return jnp.where(keep, 0.0, neg).astype(dtype)[None, None, :, :]


# ---------------------------------------------------------------------------
# Paged KV cache (serve/): block-table gather path
# ---------------------------------------------------------------------------


def _heads_mesh(heads: int):
    """The ambient mesh, where its ``tensor`` axis is >1 wide and divides
    ``heads`` (the serve engine's TP mode traces its steps inside
    ``use_mesh`` and shards every K/V pool on its heads axis); None
    without one."""
    from huggingface_sagemaker_tensorflow_distributed_tpu.parallel.mesh import (
        AXIS_TENSOR,
        maybe_current_mesh,
    )

    mesh = maybe_current_mesh()
    if mesh is None:
        return None
    tp = mesh.shape.get(AXIS_TENSOR, 1)
    return None if tp <= 1 or heads % tp else mesh


def _pin_heads(x, axis: int):
    """Under an ambient mesh with a >1 ``tensor`` axis (the serve
    engine's TP mode traces its steps inside ``use_mesh``), pin ``x``'s
    heads axis to it — the pools arrive sharded on heads, and pinning
    the gathered view keeps GSPMD's propagation deterministic instead
    of letting it re-replicate the per-step KV read (which would
    round-trip ``1/tp``-resident pools through full-size intermediates
    every decode step). No-op without an ambient mesh, a 1-wide tensor
    axis, or a non-dividing head count (the engine rejects that case
    for its own pools; other callers just stay unconstrained)."""
    from huggingface_sagemaker_tensorflow_distributed_tpu.parallel.mesh import (
        AXIS_TENSOR,
    )

    mesh = _heads_mesh(x.shape[axis])
    if mesh is None:
        return x
    from jax.sharding import NamedSharding, PartitionSpec

    spec = [None] * x.ndim
    spec[axis] = AXIS_TENSOR
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, PartitionSpec(*spec)))


def _key_major_page_rows(pool):
    """A key-major pool ``[N, bs, H, D]`` as ``[N, bs * H, D]``, a page's
    rows one a (key, head) pair, or None where that view is not free:
    a head-major pool (:func:`~.pallas_paged_attention.head_major_rows`),
    and a pool sharded on its heads (the merged axis could not carry the
    sharding). On the v5e the view is a bitcast (bf16 ``[N, 16, 2, 128]``
    tiled ``(2, 128)`` is byte for byte ``[N, 32, 128]`` tiled ``(8,
    128)``: PR 29) and it is what the compiler reads and writes a WHOLE
    page of where it lies: through the four axes it re-laid the whole pool
    out, to ``{3,1,2,0:T(8,128)}``, before a block-windowed scatter and
    back after it, and before the gather of a one-row dispatch (rehearsal
    compiles for the v5e, PR 38)."""
    from huggingface_sagemaker_tensorflow_distributed_tpu.ops.pallas_paged_attention import (
        head_major_rows,
    )

    N, bs, H, D = pool.shape
    if head_major_rows(H) or _heads_mesh(H) is not None:
        return None
    return pool.reshape(N, bs * H, D)


def gather_paged_kv(pool, block_tables, width: int | None = None):
    """Materialize per-slot contiguous KV from a paged pool.

    ``pool`` is one layer's preallocated block pool
    [num_blocks, block_size, heads, head_dim]; ``block_tables``
    [slots, blocks_per_slot] maps each decode slot's logical block index
    to a physical pool block (vLLM's block table). Returns
    [slots, heads, blocks_per_slot * block_size, head_dim] — logical
    position ``p`` of slot ``s`` lives at
    ``pool[block_tables[s, p // block_size], p % block_size]``, so the
    gathered view is position-ordered exactly like a contiguous cache
    buffer. The gather is O(context) reads per step — the same bytes a
    contiguous cache read costs; what paging changes is the PERSISTENT
    allocation, which scales with blocks actually held, not
    ``slots × max_len``.

    ``width`` (a STATIC python int, multiple of the block size) gathers
    only the first ``width`` logical token slots per row — the
    width-bucketed read path: when every resident context fits in a
    bucket far below ``max_model_len``, the step's read traffic (and
    the attention mask/logits width behind it) shrinks to the bucket
    instead of the full table span. Callers guarantee every valid
    logical position is ``< width``.

    Under a tensor-parallel serving mesh (pool sharded on its heads
    axis, block tables replicated) the gather is shard-local per kv
    head and the returned view stays heads-sharded (pinned via
    :func:`_pin_heads`) — the read never leaves the shard that will
    attend with it."""
    bs = pool.shape[1]
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
    rows = _key_major_page_rows(pool)
    g = (pool if rows is None else rows)[block_tables]
    (S, nb), (_, bs, H, D) = block_tables.shape, pool.shape
    g = g.reshape(S, nb, bs, H, D)
    return _pin_heads(g.transpose(0, 3, 1, 2, 4).reshape(S, H, nb * bs, D),
                      axis=1)


def scatter_paged_kv(pool, block_tables, positions, values):
    """Write ``values`` [n, heads, head_dim] at logical ``positions``
    [slots_or_n] of the slots owning them into the paged ``pool``
    (inverse addressing of :func:`gather_paged_kv`). ``block_tables``
    here is the [n, blocks_per_slot] table of the written slots (one row
    per written token). Callers route writes for INACTIVE slots to the
    reserved null block 0 (never allocated to a request), so a fully
    static-shape step can always scatter. This is the write of the steps
    that add ONE token a slot and have no whole block to write (the decode
    step, gathered or fused: ``serve/engine.py::_decode_step``, the paged
    branches of ``models/llama.py`` and ``models/deepseek_v2.py``; the
    speculative window), and of a prefill chunk that is no multiple of the
    block size; a prefill dispatch on the block grid writes whole blocks
    (:func:`scatter_paged_blocks`).

    Under a tensor-parallel serving mesh the write is shard-local like
    the gather: ``values`` carries the pool's heads axis (sharded by
    propagation from the model's own sharded K/V), the addressing
    operands are replicated, and the output inherits the pool operand's
    heads sharding — no collective on the write path."""
    bs = pool.shape[1]
    n = positions.shape[0]
    block_ids = jnp.take_along_axis(
        block_tables, (positions // bs)[:, None], axis=1)[:, 0]
    if pool.ndim == 4:
        from huggingface_sagemaker_tensorflow_distributed_tpu.ops.pallas_paged_attention import (
            head_major_rows,
        )

        if head_major_rows(pool.shape[2]):
            # the chip stores such a page head-major ([H][block][D]): the
            # write goes to that view's rows, one a (token, head) pair, in
            # place. Through the logical [block, H] axes the compiler
            # re-lays the whole pool out before the scatter and back after
            # it (two 437 MB copies a pool a step at 30 heads: rehearsal
            # compile for the v5e, PR 33)
            N, _, H, D = pool.shape
            rows = pool.transpose(0, 2, 1, 3).reshape(N * H * bs, D)
            at = ((block_ids[:, None] * H + jnp.arange(H)[None, :]) * bs
                  + (positions % bs)[:, None]).reshape(-1)
            rows = rows.at[at].set(values.reshape(n * H, D))
            return rows.reshape(N, H, bs, D).transpose(0, 2, 1, 3)
    return pool.at[block_ids, positions % bs].set(values)


def scatter_paged_blocks(pool, block_tables, start, values):
    """Write a prefill dispatch's chunks into the paged ``pool`` as WHOLE
    BLOCKS: ``values`` ``[G, heads, C, head_dim]`` are row ``g``'s ``C``
    tokens from logical position ``start[g]``, with ``start`` on the block
    grid and ``C`` a multiple of the block size (callers guarantee both),
    so row ``g`` owns exactly blocks ``block_tables[g, start[g] // bs :
    start[g] // bs + C // bs]`` and each gets ONE update, laid out as the
    pool stores a block: ``[bs, D]`` in a latent pool (``[N, bs, D]``;
    ``heads`` is 1), ``[H, bs, D]`` through the ``transpose(0, 2, 1, 3)``
    view where the chip stores a page head-major, else the page's ``bs *
    H`` (key, head) rows (:func:`_key_major_page_rows`), or ``[bs, H, D]``
    as it is where the heads are sharded: the update carries the heads
    axis and the write stays shard-local, as in :func:`scatter_paged_kv`.
    The same values reach the same pool rows as one
    :func:`scatter_paged_kv` row a token; XLA's scatter walks its indices
    one after another, about 70 ns a 256-byte row on the v5e and 90 ns an
    8 KB page (0.5 us a 122 KB one: 250 GB/s), so a four-row dispatch's
    128 pages a pool cost 0.8 ms over Qwen's 72 pools where 2,048 rows
    cost 10.4, and 0.5 ms over Olmo-Hybrid's 8 where 61,440 (token, head)
    rows cost 33.9 (chip runs, PR 38). A pad row rides the null table and
    writes block 0 many times over (never read)."""
    from huggingface_sagemaker_tensorflow_distributed_tpu.ops.pallas_paged_attention import (
        head_major_rows,
    )

    bs = pool.shape[1]
    G, H, C, D = values.shape
    n = C // bs
    cols = start[:, None] // bs + jnp.arange(n, dtype=start.dtype)[None, :]
    ids = jnp.take_along_axis(block_tables, cols, axis=1).reshape(G * n)
    if pool.ndim == 3:
        return pool.at[ids].set(values.reshape(G * n, bs, D))
    if head_major_rows(H):
        pages = (values.reshape(G, H, n, bs, D).transpose(0, 2, 1, 3, 4)
                 .reshape(G * n, H, bs, D))
        return (pool.transpose(0, 2, 1, 3).at[ids].set(pages)
                .transpose(0, 2, 1, 3))
    pages = values.transpose(0, 2, 1, 3)          # [G, C, H, D]: key-major
    rows = _key_major_page_rows(pool)
    if rows is None:
        return pool.at[ids].set(pages.reshape(G * n, bs, H, D))
    return (rows.at[ids].set(pages.reshape(G * n, bs * H, D))
            .reshape(pool.shape))


def paged_attention(q, k_pool, v_pool, block_tables, context_lens,
                    scale=None, width: int | None = None,
                    impl: str = "xla", window: int | None = None,
                    k_scale_pool=None, v_scale_pool=None):
    """Single-token decode attention against a paged KV pool.

    ``q`` [slots, heads, head_dim] (the step's one query per slot);
    pools/[block_tables] as in :func:`gather_paged_kv`;
    ``context_lens`` [slots] counts valid tokens per slot (the query's
    own K/V included — the query position is ``context_len - 1``).
    Keys at logical positions >= context_len (stale block tails,
    null-block junk) are masked additively — the −1e9 convention keeps
    the softmax NaN-free even for empty (context 0) slots. ``width``
    (static) restricts the gather to a context-width bucket — callers
    guarantee ``context_lens <= width``.

    ``impl='xla'`` (the reference and CPU-native path) gathers a dense
    view then attends; ``impl='pallas'`` runs the fused decode kernel
    (``ops/pallas_paged_attention.py``) that walks the block tables
    directly — no dense intermediate, interpret-mode off-TPU (context-0
    rows return zeros there instead of masked-junk softmax; callers
    discard them either way). GQA is native to both: ``q`` may carry a
    multiple of the pools' kv heads. ``window`` applies Mistral's
    sliding band (key kept iff ``0 <= q_pos - k_pos < window``).
    ``k_scale_pool``/``v_scale_pool`` ([blocks, block_size, heads, 1]
    fp32) mark int8 pools: the XLA path dequantizes the gathered view,
    the kernel dequantizes in-tile. Returns [slots, heads, head_dim]."""
    if impl == "pallas":
        from huggingface_sagemaker_tensorflow_distributed_tpu.ops.pallas_paged_attention import (
            paged_decode_attention,
        )
        return paged_decode_attention(
            q, k_pool, v_pool, block_tables, context_lens, scale=scale,
            width=width, window=window, k_scale_pool=k_scale_pool,
            v_scale_pool=v_scale_pool)
    if impl != "xla":
        raise ValueError(f"unknown paged_attention impl {impl!r} "
                         "(xla | pallas)")
    k = gather_paged_kv(k_pool, block_tables, width=width)
    v = gather_paged_kv(v_pool, block_tables, width=width)
    if k_scale_pool is not None:
        ks = gather_paged_kv(k_scale_pool, block_tables, width=width)
        vs = gather_paged_kv(v_scale_pool, block_tables, width=width)
        k = (k.astype(jnp.float32) * ks).astype(q.dtype)
        v = (v.astype(jnp.float32) * vs).astype(q.dtype)
    if k.shape[1] != q.shape[1]:
        # GQA: repeat the gathered kv heads to the query's head count
        # (the kernel path groups queries instead — no repeat exists)
        rep = q.shape[1] // k.shape[1]
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    max_ctx = k.shape[2]
    pos = jnp.arange(max_ctx)[None, :]
    valid = pos < context_lens[:, None]
    if window is not None:
        valid = valid & (pos > context_lens[:, None] - 1 - window)
    mask = jnp.where(valid, 0.0, -1e9)[:, None, None, :]
    return xla_attention(q[:, :, None, :], k, v, mask=mask,
                         scale=scale)[:, :, 0, :]


def relative_position_bucket(relative_position, bidirectional: bool,
                             num_buckets: int, max_distance: int):
    """HF ``T5Attention._relative_position_bucket`` semantics: log-spaced
    buckets beyond ``num_buckets // 2``, sign split when bidirectional.
    Lives here (dep-free) so both the T5 model and the ring-attention
    kernel can bucket from global positions."""
    ret = jnp.zeros_like(relative_position)
    if bidirectional:
        num_buckets //= 2
        ret += (relative_position > 0).astype(jnp.int32) * num_buckets
        rp = jnp.abs(relative_position)
    else:
        rp = -jnp.minimum(relative_position, 0)
    max_exact = num_buckets // 2
    is_small = rp < max_exact
    large = max_exact + (
        jnp.log(rp.astype(jnp.float32) / max_exact + 1e-9)
        / math.log(max_distance / max_exact)
        * (num_buckets - max_exact)
    ).astype(jnp.int32)
    large = jnp.minimum(large, num_buckets - 1)
    return ret + jnp.where(is_small, rp, large)


def relative_position_bias(table, q_pos, kv_pos, bidirectional: bool,
                           num_buckets: int, max_distance: int):
    """[1, heads, q, kv] fp32 additive bias from a [num_buckets, heads]
    embedding table and global position grids ``q_pos`` [q, 1] /
    ``kv_pos`` [1, kv] — the tile form ring attention computes per step."""
    buckets = relative_position_bucket(
        kv_pos - q_pos, bidirectional=bidirectional,
        num_buckets=num_buckets, max_distance=max_distance)
    values = jnp.take(table.astype(jnp.float32), buckets, axis=0)  # [q, kv, h]
    return values.transpose(2, 0, 1)[None]
