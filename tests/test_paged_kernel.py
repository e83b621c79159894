"""Pallas fused paged-attention decode kernel
(``ops/pallas_paged_attention.py``) vs the XLA gather reference
(``ops.attention.paged_attention(impl='xla')``), run in interpret mode
on CPU — the hardware-free correctness story the ISSUE 9 acceptance
names: width buckets × GQA groupings × fp/int8 pools × sliding-window
bands. Plus the int8 scatter/gather scale-path contracts the pools are
built on: quantize→scatter→gather/dequant roundtrip error bounds, the
null-block-0 zero-scale convention, and COW copying the int8 block AND
its scale rows atomically."""

import numpy as np
import pytest


def _pools(rng, N, bs, Hkv, D):
    import jax.numpy as jnp

    pk = jnp.asarray(rng.randn(N, bs, Hkv, D).astype(np.float32))
    pv = jnp.asarray(rng.randn(N, bs, Hkv, D).astype(np.float32))
    return pk, pv


def _quantized(rng, pool):
    """An int8 pool + positive scale plane whose dequantized value is
    the reference fp pool for parity checks."""
    import jax.numpy as jnp

    scale = jnp.asarray(
        0.05 + np.abs(rng.randn(*pool.shape[:3], 1)).astype(np.float32))
    q = jnp.clip(jnp.round(pool / scale), -127, 127).astype(jnp.int8)
    return q, scale, q.astype(jnp.float32) * scale


def _xla_ref(q, pk, pv, tables, ctx, width=None, window=None):
    from huggingface_sagemaker_tensorflow_distributed_tpu.ops.attention import (
        paged_attention,
    )

    return paged_attention(q, pk, pv, tables, ctx, width=width,
                           impl="xla", window=window)


def _kernel(q, pk, pv, tables, ctx, width=None, window=None, ks=None,
            vs=None):
    from huggingface_sagemaker_tensorflow_distributed_tpu.ops.pallas_paged_attention import (
        paged_decode_attention,
    )

    return paged_decode_attention(q, pk, pv, tables, ctx, width=width,
                                  window=window, k_scale_pool=ks,
                                  v_scale_pool=vs)


@pytest.fixture
def two_page_blocks(monkeypatch):
    """The kernel's one derived compute block, shrunk to TWO of the
    cell's pages (32 keys) so a 128-key table holds several."""
    from huggingface_sagemaker_tensorflow_distributed_tpu.ops import (
        pallas_paged_attention,
    )

    monkeypatch.setattr(pallas_paged_attention, "_BLOCK_KEYS",
                        2 * _CELL["bs"])


def _assert_close(got, want, ctx):
    """Active rows match to tolerance; the kernel's context-0 rows are
    exact zeros (the XLA path emits masked-junk softmax there — both
    discarded by callers)."""
    act = np.asarray(ctx) > 0
    np.testing.assert_allclose(np.asarray(got)[act], np.asarray(want)[act],
                               rtol=1e-5, atol=1e-5)
    assert np.all(np.asarray(got)[~act] == 0.0)


# chat-sat's geometry cut small (PR 29): 2 KV heads x 8 query heads a
# group, heads of 128, blocks of 16, a compute block of TWO pages (32
# keys). One batch holds every edge of the walk: an empty slot, one key,
# exactly one page, exactly one compute block, one key past a compute
# block, and the bucket's full width.
_CELL = dict(Hkv=2, G=8, D=128, bs=16)


def _cell_contexts(width):
    return np.array([0, 1, 16, 32, 33, width], np.int32)


def _cell_case(rng, width, int8):
    """(q, pools as stored, pools as their values, scales, tables, ctx)
    at the cell's geometry for the bucket ``width``; the block table
    spans 128 keys whatever the bucket."""
    import jax.numpy as jnp

    Hkv, G, D, bs = (_CELL[k] for k in ("Hkv", "G", "D", "bs"))
    ctx = _cell_contexts(width)
    S, nb = len(ctx), 128 // bs
    N = 1 + S * nb
    pk, pv = _pools(rng, N, bs, Hkv, D)
    tables = jnp.asarray(rng.permutation(np.arange(1, N))
                         .reshape(S, nb).astype(np.int32))
    q = jnp.asarray(rng.randn(S, Hkv * G, D).astype(np.float32) * 0.3)
    if not int8:
        return q, (pk, pv), (pk, pv), {}, tables, jnp.asarray(ctx)
    qk, ks, dk = _quantized(rng, pk)
    qv, vs, dv = _quantized(rng, pv)
    return (q, (qk, qv), (dk, dv), dict(ks=ks, vs=vs), tables,
            jnp.asarray(ctx))


@pytest.mark.parametrize("case", [
    "smoke",
    "cell-b64-fp", "cell-b64-fp-window", "cell-b64-int8",
    "cell-b64-int8-window",
    "cell-b128-fp", "cell-b128-fp-window", "cell-b128-int8",
    "cell-b128-int8-window",
])
def test_paged_kernel_smoke_matches_xla(case, two_page_blocks):
    """Tier-1 parity (interpret mode against the gather path): one small
    fp GQA case, and the cell's geometry cut small at two buckets, fp and
    int8 pools, with and without a window whose band starts inside a
    compute block — the full matrix runs under the slow tier."""
    import jax.numpy as jnp

    if case == "smoke":
        rng = np.random.RandomState(0)
        S, Hq, Hkv, D, bs, nb = 3, 4, 2, 8, 4, 4
        pk, pv = _pools(rng, 1 + S * nb, bs, Hkv, D)
        tables = jnp.asarray(rng.permutation(np.arange(1, 1 + S * nb))
                             .reshape(S, nb).astype(np.int32))
        q = jnp.asarray(rng.randn(S, Hq, D).astype(np.float32))
        ctx = jnp.asarray(np.array([5, 16, 0], np.int32))
        got = _kernel(q, pk, pv, tables, ctx, width=16)
        want = _xla_ref(q, pk, pv, tables, ctx, width=16)
        _assert_close(got, want, ctx)
        return
    _, bucket, kind, *rest = case.split("-")
    width, window = int(bucket[1:]), (24 if rest else None)
    q, stored, values, scales, tables, ctx = _cell_case(
        np.random.RandomState(7), width, kind == "int8")
    got = _kernel(q, *stored, tables, ctx, width=width, window=window,
                  **scales)
    want = _xla_ref(q, *values, tables, ctx, width=width, window=window)
    _assert_close(got, want, ctx)


@pytest.mark.parametrize("kind", ["fp", "int8"])
def test_pages_past_the_context_are_not_read(kind, two_page_blocks):
    """Junk (NaN) in every pool block a slot's context does not reach
    must not change its output: the walk ends at the context's last
    page, an empty slot fetches nothing, and the null block is never
    touched. (int8 values cannot hold a NaN: their scales do, over
    values of 127.)"""
    import jax.numpy as jnp

    width = 128
    q, stored, values, scales, tables, ctx = _cell_case(
        np.random.RandomState(8), width, kind == "int8")
    want = _xla_ref(q, *values, tables, ctx, width=width)
    bs = _CELL["bs"]
    reached = np.zeros((stored[0].shape[0],), bool)
    for row, n in zip(np.asarray(tables), np.asarray(ctx)):
        reached[row[:-(-int(n) // bs)]] = True
    junk = jnp.asarray(~reached)[:, None, None, None]
    if kind == "int8":
        stored = tuple(jnp.where(junk, jnp.int8(127), p) for p in stored)
        scales = {k: jnp.where(junk, jnp.nan, v) for k, v in scales.items()}
    else:
        stored = tuple(jnp.where(junk, jnp.nan, p) for p in stored)
    got = _kernel(q, *stored, tables, ctx, width=width, **scales)
    assert np.isfinite(np.asarray(got)).all()
    _assert_close(got, want, ctx)


@pytest.mark.parametrize("shape, pages", [
    # (block size, KV heads, head size, item bytes, pool blocks, int8 on chip)
    ((16, 2, 128, 2, 7629, False), 32),     # chat-sat's: 512 keys
    ((16, 8, 128, 2, 4000, False), 32),     # Llama-3-8B's: 1 MiB exactly
    ((16, 32, 128, 2, 4000, False), 8),     # Llama-2-7B's: 1 MiB is 128 keys
    ((16, 32, 128, 4, 4000, False), 4),
    ((16, 8, 128, 4, 4000, False), 16),
    ((16, 2, 128, 1, 7629, True), 32),
    ((24, 1, 128, 1, 4000, True), 16),      # 21 pages cut to whole lane tiles
    ((16, 2, 128, 2, 5, False), 5),         # no more than the pool has
    ((16, 128, 256, 4, 4000, False), 1),    # a page over the budget: one
])
def test_compute_block_is_bounded_in_keys_and_bytes(shape, pages):
    """The compute block is derived, not set: 512 keys, at most 1 MiB of
    a pool (so VMEM does not grow with the KV heads: 32 of them compile
    where 2 do), at most the pool; int8 pools on the chip take whole
    lane tiles of rows or raise (REVIEW of PR 29)."""
    from huggingface_sagemaker_tensorflow_distributed_tpu.ops.pallas_paged_attention import (
        _BLOCK_BYTES,
        block_pages,
    )

    got = block_pages(*shape)
    assert got == pages
    bs, Hkv, D, itemsize, _, lanes = shape
    assert got == 1 or got * bs * Hkv * D * itemsize <= _BLOCK_BYTES
    if lanes:
        assert got * bs * Hkv % 128 == 0
    with pytest.raises(ValueError, match="multiple of 128 rows"):
        block_pages(16, 1, 128, 1, 4, True)


def test_many_kv_heads_match_xla_with_the_block_cut_by_bytes():
    """Llama-2-7B's heads (32 KV heads of 128, no grouping): a page of
    float32 is 256 KiB, so a compute block is 4 pages by the byte bound
    and a 128-key context is two of them."""
    import jax.numpy as jnp

    from huggingface_sagemaker_tensorflow_distributed_tpu.ops.pallas_paged_attention import (
        block_pages,
    )

    rng = np.random.RandomState(9)
    S, Hkv, D, bs, nb = 3, 32, 128, 16, 8
    N = 1 + S * nb
    assert block_pages(bs, Hkv, D, 4, N) == 4
    pk, pv = _pools(rng, N, bs, Hkv, D)
    tables = jnp.asarray(rng.permutation(np.arange(1, N))
                         .reshape(S, nb).astype(np.int32))
    q = jnp.asarray(rng.randn(S, Hkv, D).astype(np.float32) * 0.3)
    ctx = jnp.asarray(np.array([65, 128, 0], np.int32))
    _assert_close(_kernel(q, pk, pv, tables, ctx, width=128),
                  _xla_ref(q, pk, pv, tables, ctx, width=128), ctx)


@pytest.mark.parametrize("group", [1, 4])
def test_paged_kernel_matrix_matches_xla(group):
    """The acceptance matrix: every (width bucket × sliding window)
    combination, fp AND int8 pools, at GQA group sizes 1 (MHA) and 4 —
    kernel output == XLA gather path to tolerance on active rows."""
    import jax.numpy as jnp

    rng = np.random.RandomState(1)
    Hkv, D, bs, nb = 2, 16, 4, 8
    Hq = Hkv * group
    S = 5
    N = 1 + S * nb
    pk, pv = _pools(rng, N, bs, Hkv, D)
    qk, ks, dk = _quantized(rng, pk)
    qv, vs, dv = _quantized(rng, pv)
    tables = jnp.asarray(rng.permutation(np.arange(1, N))
                         .reshape(S, nb).astype(np.int32))
    q = jnp.asarray(rng.randn(S, Hq, D).astype(np.float32))
    base = np.array([1, 7, 13, 32, 0], np.int32)
    for width in (None, 8, 16):
        W = width or bs * nb
        ctx = jnp.asarray(np.minimum(base, W))
        for window in (None, 3, 11):
            got = _kernel(q, pk, pv, tables, ctx, width=width,
                          window=window)
            want = _xla_ref(q, pk, pv, tables, ctx, width=width,
                            window=window)
            _assert_close(got, want, ctx)
            # int8 pools: in-kernel dequant == dequantize-then-attend
            got8 = _kernel(q, qk, qv, tables, ctx, width=width,
                           window=window, ks=ks, vs=vs)
            want8 = _xla_ref(q, dk, dv, tables, ctx, width=width,
                             window=window)
            _assert_close(got8, want8, ctx)


def test_paged_kernel_validates_inputs():
    import jax.numpy as jnp

    from huggingface_sagemaker_tensorflow_distributed_tpu.ops.attention import (
        paged_attention,
    )

    rng = np.random.RandomState(2)
    pk, pv = _pools(rng, 9, 4, 2, 8)
    tables = jnp.zeros((2, 2), jnp.int32)
    ctx = jnp.zeros((2,), jnp.int32)
    q3 = jnp.asarray(rng.randn(2, 3, 8).astype(np.float32))
    with pytest.raises(ValueError, match="multiple of pool kv heads"):
        _kernel(q3, pk, pv, tables, ctx)
    q = jnp.asarray(rng.randn(2, 4, 8).astype(np.float32))
    with pytest.raises(ValueError, match="multiple"):
        _kernel(q, pk, pv, tables, ctx, width=6)
    with pytest.raises(ValueError, match="block table holds"):
        _kernel(q, pk, pv, tables, ctx, width=16)
    with pytest.raises(ValueError, match="BOTH"):
        _kernel(q, pk, pv, tables, ctx, ks=jnp.zeros((9, 4, 2, 1)))
    with pytest.raises(ValueError, match="unknown paged_attention impl"):
        paged_attention(q, pk, pv, tables, ctx, impl="cuda")


# -- int8 scatter/gather scale path (the pools the kernel reads) -------------

def test_int8_scatter_gather_roundtrip_error_bound():
    """quantize → scatter (values + scales) → gather/dequant recovers
    the original K/V within the symmetric-int8 bound (scale/2 per
    element), and EXACTLY at zero."""
    import jax.numpy as jnp

    from huggingface_sagemaker_tensorflow_distributed_tpu.models.llama import (
        kv_quantize,
    )
    from huggingface_sagemaker_tensorflow_distributed_tpu.ops.attention import (
        gather_paged_kv,
        scatter_paged_kv,
    )

    rng = np.random.RandomState(3)
    B, H, D, bs, nb = 2, 3, 8, 4, 2
    N = 1 + B * nb
    pool = jnp.zeros((N, bs, H, D), jnp.int8)
    scale_pool = jnp.zeros((N, bs, H, 1), jnp.float32)
    tables = jnp.asarray(np.arange(1, N).reshape(B, nb).astype(np.int32))
    vals = rng.randn(B, H, bs * nb, D).astype(np.float32) * 3.0
    vals[0, :, 2] = 0.0                        # a zero row stays exact
    for p in range(bs * nb):
        x = jnp.asarray(vals[:, :, p:p + 1, :])     # [B, H, 1, D]
        qx, sx = kv_quantize(x)
        pos = jnp.full((B,), p, jnp.int32)
        pool = scatter_paged_kv(pool, tables, pos, qx[:, :, 0, :])
        scale_pool = scatter_paged_kv(scale_pool, tables, pos,
                                      sx[:, :, 0, :])
    got = (np.asarray(gather_paged_kv(pool, tables)).astype(np.float32)
           * np.asarray(gather_paged_kv(scale_pool, tables)))
    scales = np.abs(vals).max(axis=-1, keepdims=True) / 127.0
    assert np.all(np.abs(got - vals) <= scales / 2 + 1e-7)
    np.testing.assert_array_equal(got[0, :, 2], 0.0)


def test_null_block_zero_scale_convention():
    """Block 0 (the null block inactive slots scatter to) starts at
    int8 0 with scale 0: a gather that reads it dequantizes to EXACT
    zeros, never junk — and writes routed there never touch real
    blocks."""
    import jax.numpy as jnp

    from huggingface_sagemaker_tensorflow_distributed_tpu.ops.attention import (
        gather_paged_kv,
        scatter_paged_kv,
    )

    pool = jnp.zeros((4, 2, 2, 4), jnp.int8)
    scale_pool = jnp.zeros((4, 2, 2, 1), jnp.float32)
    real = pool.at[2].set(7)
    # an inactive slot's write routed to the null table row
    null_tables = jnp.zeros((1, 2), jnp.int32)
    written = scatter_paged_kv(real, null_tables,
                               jnp.zeros((1,), jnp.int32),
                               jnp.full((1, 2, 4), 5, jnp.int8))
    assert np.all(np.asarray(written[2]) == 7)          # real untouched
    deq = (np.asarray(gather_paged_kv(pool, null_tables))
           .astype(np.float32)
           * np.asarray(gather_paged_kv(scale_pool, null_tables)))
    np.testing.assert_array_equal(deq, 0.0)


def test_cow_copies_int8_block_and_scale_rows_atomically():
    """The engine's COW device copy must duplicate EVERY pool a block
    addresses — under int8 that is the int8 K/V pools AND their fp32
    scale pools in the same ``_apply_cow`` application, or a privatized
    block would dequantize with another request's scales."""
    import dataclasses

    import jax.numpy as jnp

    from huggingface_sagemaker_tensorflow_distributed_tpu.models.auto import (
        init_params,
    )
    from huggingface_sagemaker_tensorflow_distributed_tpu.models.gpt2 import (
        Gpt2Config,
        Gpt2LMHeadModel,
    )
    from huggingface_sagemaker_tensorflow_distributed_tpu.serve.engine import (
        ServeEngine,
    )

    cfg = Gpt2Config(vocab_size=64, hidden_size=16, num_layers=1,
                     num_heads=2, intermediate_size=32,
                     max_position_embeddings=64, hidden_dropout=0.0,
                     embd_dropout=0.0, attention_dropout=0.0,
                     eos_token_id=63, pad_token_id=0,
                     kv_cache_dtype="int8")
    model = Gpt2LMHeadModel(cfg)
    params = init_params(model, cfg, seed=0)
    eng = ServeEngine(model, params, num_slots=2, block_size=4,
                      num_blocks=8, prefill_chunk=4, max_model_len=16,
                      prefix_cache=True)
    dtypes = {str(p.dtype) for p in eng._pools}
    assert dtypes == {"int8", "float32"}       # values + scale planes
    # poison block 1 across every pool, then COW-copy it to block 2
    eng._pools = [p.at[1].set(3 if p.dtype == jnp.int8 else 0.5)
                  for p in eng._pools]

    class _Slot:
        pending_copies = [(1, 2)]

    slot = _Slot()
    eng._apply_cow(slot)
    assert slot.pending_copies == []
    for p in eng._pools:
        np.testing.assert_array_equal(np.asarray(p[2]), np.asarray(p[1]))
