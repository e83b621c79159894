"""Pallas fused paged-attention decode kernel: single-token decode
attention that walks per-slot block tables DIRECTLY, with optional
in-kernel int8 KV dequantization.

The serving engine's decode hot path was two HBM round-trips:
``ops.attention.gather_paged_kv`` materializes a dense
``[slots, H, width, D]`` view of each slot's paged KV, then the model
attends over it — at long context the step is bound by KV bytes moved,
not FLOPs (the read-amplification PagedAttention's motivating analysis
names; Kwon et al. 2023 pay a single fused read here). This kernel
folds the gather into the attention read:

- **grid** ``(slot, context_block)`` with the context-block axis
  innermost, so the online-softmax state (running max / sum / output
  accumulator, Dao et al. 2022 — the same recurrence
  ``ops/pallas_attention.py`` blocks over) lives in VMEM scratch across
  one slot's context walk. One tile holds ALL kv heads of one pool
  block, ``(1, block_size, H_kv, D)``: Mosaic requires a block's last
  two dimensions to equal the array's or be multiples of (8, 128), and
  a one-head slice of a ``[blocks, block_size, H_kv, D]`` pool is
  neither (the shape this kernel had until it first met the compiler);
- **block-table indirection in the BlockSpec index maps**: the tables
  (and per-slot context lengths) ride scalar prefetch
  (``pltpu.PrefetchScalarGridSpec``), so tile ``i`` of slot ``s`` DMAs
  pool block ``tables[s, i]`` straight from the paged pool — no dense
  intermediate ever exists in HBM;
- **vector-unit arithmetic, heads on sublanes**: a decode query is one
  row per head, so QKᵀ and PV are a lane reduction and a block-axis
  reduction over the ``[block_size, H_kv, D]`` tile instead of
  M=1 matmuls — every head advances in the same instruction and no
  per-head slice or relayout of the tile is needed;
- **context masking in-kernel**: keys at logical positions ≥
  ``context_lens[s]`` (stale block tails, null-block junk) are masked
  to −1e30 in-tile, and whole tiles past the context skip compute via
  ``pl.when`` (the dynamic analogue of ``pallas_attention._tile_runs``
  — the grid is static per width bucket, the work is not);
- **GQA query grouping**: the ``H // H_kv`` query heads of one KV head
  attend against the same resident tile (a static loop over the group),
  so grouped-query models read each KV block exactly once — the repeat
  the XLA path materializes never happens;
- **sliding-window banding**: with ``window`` set, tiles entirely
  BELOW the band (newest key ≤ ``ctx − 1 − window``) skip compute too
  — the banded-tile inequality of ``_tile_runs``, driven by the
  dynamic per-slot context — and in-band tiles mask per position;
- **in-tile int8 dequant**: with scale pools given, K/V tiles load as
  int8 (+ the fp32 per-(position, head) scale rows riding the same
  block-table index maps) and dequantize in VMEM — int8 pools halve
  the KV bytes per decode step END TO END, not just in storage.

Numerics match the XLA gather path (``ops.attention.paged_attention``):
fp32 logits and softmax statistics, fp32 PV accumulation, output cast
to the query dtype. Inactive rows (``context_len == 0``) return ZEROS
(the XLA path returns a softmax over fully-masked junk instead —
callers discard those rows either way).

Correctness is testable without TPU hardware via
``pallas_call(interpret=True)`` — ``tests/test_paged_kernel.py`` pins
kernel-vs-XLA parity across width buckets, GQA groupings, int8/fp
pools, and sliding-window bands, and ``tests/test_serve.py`` pins
engine-level token-exactness vs ``generate_causal`` with the kernel
engaged.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def _paged_kernel(tbl_ref, ctx_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref,
                  o_ref, acc_ref, m_ref, l_ref, *, scale, block_size,
                  window, groups):
    """One (slot, context_block) tile over all kv heads.
    ``tbl_ref``/``ctx_ref`` are the scalar-prefetched block tables /
    context lengths (also consumed by the BlockSpec index maps — the
    gather indirection); ``ks_ref``/``vs_ref`` are None on fp pools.
    ``q_ref``/``o_ref`` are ``[1, G, H_kv, D]`` and the scratch
    ``[G, H_kv, ·]``: group-major, so one group's heads are a leading
    index away."""
    s_idx = pl.program_id(0)
    i = pl.program_id(1)
    num_blocks = pl.num_programs(1)

    @pl.when(i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    ctx = ctx_ref[s_idx]
    start = i * block_size
    # tiles fully past the context hold no valid key; with a sliding
    # window, tiles fully BELOW the band (newest key ≤ ctx-1-window)
    # hold none either — the dynamic form of _tile_runs' band check
    run = start < ctx
    if window is not None:
        run = jnp.logical_and(run, start + block_size > ctx - window)

    @pl.when(run)
    def _step():
        k = k_ref[0].astype(jnp.float32)                  # [bs, Hkv, D]
        v = v_ref[0].astype(jnp.float32)
        if ks_ref is not None:
            # in-tile dequant: int8 block × fp32 per-(pos, head) scale
            k = k * ks_ref[0]                             # [bs, Hkv, 1]
            v = v * vs_ref[0]
        pos = start + jax.lax.broadcasted_iota(
            jnp.int32, (k.shape[0], k.shape[1], 1), 0)
        keep = pos < ctx
        if window is not None:
            # the decode query sits at position ctx-1: Mistral's band
            # keeps key j iff 0 <= (ctx-1) - j < window
            keep = jnp.logical_and(keep, pos > ctx - 1 - window)
        for g in range(groups):
            q = q_ref[0, g].astype(jnp.float32)           # [Hkv, D]
            s_log = jnp.sum(k * q[None], axis=-1,
                            keepdims=True) * scale        # [bs, Hkv, 1]
            s_log = jnp.where(keep, s_log, _NEG_INF)
            m_prev = m_ref[g][:, :1][None]                # [1, Hkv, 1]
            l_prev = l_ref[g][:, :1][None]
            m_new = jnp.maximum(
                m_prev, jnp.max(s_log, axis=0, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s_log - m_new)                    # [bs, Hkv, 1]
            l_new = alpha * l_prev + jnp.sum(p, axis=0, keepdims=True)
            m_ref[g] = jnp.broadcast_to(m_new[0], m_ref.shape[1:])
            l_ref[g] = jnp.broadcast_to(l_new[0], l_ref.shape[1:])
            acc_ref[g] = acc_ref[g] * alpha[0] + jnp.sum(p * v, axis=0)

    @pl.when(i == num_blocks - 1)
    def _finish():
        for g in range(groups):
            l = l_ref[g][:, :1]
            # a context-0 (inactive) row runs no tile: l == 0, output 0
            safe_l = jnp.where(l == 0.0, 1.0, l)
            o_ref[0, g] = (acc_ref[g] / safe_l).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("scale", "window", "interpret", "int8"))
def _paged_call(q, k_pool, v_pool, block_tables, context_lens,
                k_scale_pool, v_scale_pool, scale, window, interpret,
                int8):
    S, Hq, D = q.shape
    _, bs, Hkv, _ = k_pool.shape
    G = Hq // Hkv
    nb = block_tables.shape[1]
    qg = q.reshape(S, Hkv, G, D).transpose(0, 2, 1, 3)    # [S, G, Hkv, D]

    # index maps receive the scalar-prefetch refs after the grid ids:
    # the kv maps read the BLOCK TABLE to pick the pool block each tile
    # DMAs — the gather, folded into the attention read
    def q_map(s, i, tbl, ctx):
        return (s, 0, 0, 0)

    def kv_map(s, i, tbl, ctx):
        return (tbl[s, i], 0, 0, 0)

    in_specs = [
        pl.BlockSpec((1, G, Hkv, D), q_map),
        pl.BlockSpec((1, bs, Hkv, D), kv_map),
        pl.BlockSpec((1, bs, Hkv, D), kv_map),
    ]
    args = [qg, k_pool, v_pool]
    if int8:
        in_specs += [pl.BlockSpec((1, bs, Hkv, 1), kv_map),
                     pl.BlockSpec((1, bs, Hkv, 1), kv_map)]
        args += [k_scale_pool, v_scale_pool]

    def kernel(*refs):
        if int8:
            tbl, ctx, q_, k_, v_, ks_, vs_, o_, acc_, m_, l_ = refs
        else:
            tbl, ctx, q_, k_, v_, o_, acc_, m_, l_ = refs
            ks_ = vs_ = None
        _paged_kernel(tbl, ctx, q_, k_, v_, ks_, vs_, o_, acc_, m_, l_,
                      scale=scale, block_size=bs, window=window, groups=G)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(S, nb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, G, Hkv, D), q_map),
        scratch_shapes=[
            pltpu.VMEM((G, Hkv, D), jnp.float32),     # output accumulator
            pltpu.VMEM((G, Hkv, 128), jnp.float32),   # running max (lanes)
            pltpu.VMEM((G, Hkv, 128), jnp.float32),   # running sum (lanes)
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, G, Hkv, D), q.dtype),
        interpret=interpret,
        name="paged_decode",
    )(block_tables.astype(jnp.int32), context_lens.astype(jnp.int32),
      *args)
    return out.transpose(0, 2, 1, 3).reshape(S, Hq, D)


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
    :func:`~.attention.gather_paged_kv`; ``window`` applies Mistral's
    sliding band (key kept iff ``0 <= q_pos - k_pos < window``) with
    below-band tiles skipped entirely. GQA is native: query heads must
    be a multiple of pool kv heads. Returns [slots, heads, head_dim];
    context-0 rows return zeros."""
    if (k_scale_pool is None) != (v_scale_pool is None):
        raise ValueError("int8 pools need BOTH k_scale_pool and "
                         "v_scale_pool (or neither)")
    int8 = k_scale_pool is not None
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
    return _paged_call(q, k_pool, v_pool, block_tables, context_lens,
                       k_scale_pool, v_scale_pool, float(scale),
                       window, interpret, int8)
