"""Ring attention: sequence-parallel exact attention over the ``seq`` mesh axis.

Long-context substrate. The reference has no sequence parallelism at all —
it *truncates* to 512 tokens (reference ``scripts/train.py:76,81``;
SURVEY.md §5.7) — so this subsystem is pure capability headroom: it makes
sequence length a shardable mesh axis, letting attention scale past one
chip's HBM with exact (not approximate) results.

Design (blockwise/online-softmax formulation, as in Ring Attention
[Liu et al.] and Flash Attention):

- Each ``seq``-shard holds its local Q block permanently and a rotating
  K/V (+mask) block.
- Per ring step: compute the local-Q × current-KV logits tile, fold it
  into running (max, denominator, numerator) statistics in fp32, then
  ``ppermute`` the KV block to the next neighbour. After ``seq_size``
  steps every Q block has seen every KV block; the normalized numerator
  equals exact softmax attention.
- On TPU the ``ppermute`` rides ICI neighbour links (the mesh builder
  keeps the ``seq`` axis innermost/adjacent, ``parallel/mesh.py``), and
  XLA overlaps the permute with the einsums — communication hides behind
  compute for realistic block sizes.

Composition with the other axes: batch stays sharded over (data, fsdp)
and heads over tensor, so ring attention composes with DP/FSDP/TP —
one shard_map, four parallelism axes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from huggingface_sagemaker_tensorflow_distributed_tpu.parallel.collectives import (
    ppermute_shift,
)
from huggingface_sagemaker_tensorflow_distributed_tpu.parallel.mesh import (
    AXIS_SEQ,
    AXIS_TENSOR,
    data_axis_names,
)

_NEG_INF = float("-inf")


def _ring_body(q32, scale, axis_name, n, causal, sq, my_idx, rel=None,
               rel_table=None):
    """Returns the fori_loop body folding one KV block into the stats.

    ``rel`` = (bidirectional, num_buckets, max_distance) + ``rel_table``
    [num_buckets, local_heads] enables T5-style relative-position bias:
    the [sq, sk] bias tile for the current ring step is recomputed from
    global positions, so the full [S, S] bias never materializes."""

    def body(i, carry):
        m, l, o, k, v, mask = carry
        logits = jnp.einsum(
            "bhqd,bhkd->bhqk", q32, k.astype(jnp.float32),
            preferred_element_type=jnp.float32) * scale
        if mask is not None:
            logits = logits + mask.astype(jnp.float32)
        needs_pos = causal or rel is not None
        if needs_pos:
            # global positions: our Q block is fixed at my_idx; the KV
            # block we hold at ring step i started at shard (my_idx + i).
            kv_idx = jax.lax.rem(my_idx + i, n)
            q_pos = my_idx * sq + jnp.arange(sq)[:, None]
            kv_pos = kv_idx * k.shape[2] + jnp.arange(k.shape[2])[None, :]
        if rel is not None:
            from huggingface_sagemaker_tensorflow_distributed_tpu.ops.attention import (
                relative_position_bias,
            )
            bidirectional, num_buckets, max_distance = rel
            logits = logits + relative_position_bias(
                rel_table, q_pos, kv_pos, bidirectional=bidirectional,
                num_buckets=num_buckets, max_distance=max_distance)
        if causal:
            logits = jnp.where(q_pos >= kv_pos, logits, _NEG_INF)
        blk_max = jnp.max(logits, axis=-1, keepdims=True)
        new_m = jnp.maximum(m, blk_max)
        # -inf - -inf guards: a fully-masked running max / block
        # contributes exactly zero instead of NaN
        corr = jnp.where(m == _NEG_INF, 0.0, jnp.exp(m - new_m))
        e = jnp.where(logits == _NEG_INF, 0.0,
                      jnp.exp(logits - jnp.where(new_m == _NEG_INF, 0.0, new_m)))
        l = l * corr + jnp.sum(e, axis=-1, keepdims=True)
        o = o * corr + jnp.einsum(
            "bhqk,bhkd->bhqd", e, v.astype(jnp.float32),
            preferred_element_type=jnp.float32)
        # each device hands its KV block to the previous neighbour, so at
        # ring step i we hold the block that started at shard my_idx + i
        k = ppermute_shift(k, axis_name, shift=-1)
        v = ppermute_shift(v, axis_name, shift=-1)
        if mask is not None:
            mask = ppermute_shift(mask, axis_name, shift=-1)
        return new_m, l, o, k, v, mask

    return body


def _ring_shard(q, k, v, mask, rel_table=None, *, scale, axis_name, causal,
                rel=None):
    """Per-shard ring attention. q/k/v: local [b, h, s_local, d]; mask:
    local additive [b, 1, 1, kv_local] or None; rel_table: local
    [num_buckets, h] bias table or None. Stats kept in fp32."""
    n = jax.lax.axis_size(axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    b, h, sq, d = q.shape
    q32 = q.astype(jnp.float32)
    m0 = jnp.full((b, h, sq, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, sq, 1), jnp.float32)
    o0 = jnp.zeros((b, h, sq, d), jnp.float32)
    body = _ring_body(q32, scale, axis_name, n, causal, sq, my_idx,
                      rel=rel, rel_table=rel_table)
    m, l, o, *_ = jax.lax.fori_loop(0, n, body, (m0, l0, o0, k, v, mask))
    return (o / jnp.maximum(l, 1e-30)).astype(q.dtype)


def ring_attention(q, k, v, mask=None, scale=None, *, mesh: Mesh,
                   causal: bool = False, rel_bias_table=None,
                   rel_bias_spec: tuple | None = None):
    """Exact attention with the sequence dim sharded over the ``seq`` axis.

    q, k, v: GLOBAL [batch, heads, seq, head_dim] (inside jit).
    mask: optional additive padding mask broadcastable to
    [batch, 1, 1, seq] (the ``ops.attention.make_attention_mask``
    contract). General [b, h, q, k] masks are not supported here — use
    ``causal=True`` for autoregressive masking, and
    ``rel_bias_table`` [num_buckets, heads] +
    ``rel_bias_spec`` (bidirectional, num_buckets, max_distance) for
    T5-style relative-position bias; both are recomputed per ring step
    from global positions, so they stay O(local²) per shard and the full
    [S, S] mask/bias never materializes.

    Returns GLOBAL [batch, heads, seq, head_dim], sequence-sharded.
    """
    head_dim = q.shape[-1]
    scale = scale if scale is not None else head_dim ** -0.5
    seq_size = mesh.shape.get(AXIS_SEQ, 1)
    if q.shape[2] % max(seq_size, 1) != 0:
        raise ValueError(
            f"seq len {q.shape[2]} not divisible by seq axis {seq_size}")

    batch_axes = data_axis_names()   # incl. dcn: batch stays sharded
    qkv_spec = P(batch_axes, AXIS_TENSOR, AXIS_SEQ, None)
    in_specs = [qkv_spec, qkv_spec, qkv_spec]
    args = [q, k, v]
    has_mask = mask is not None
    has_rel = rel_bias_table is not None
    if has_mask:
        mask = jnp.broadcast_to(
            mask, (q.shape[0], 1, 1, k.shape[2])).astype(jnp.float32)
        in_specs.append(P(batch_axes, None, None, AXIS_SEQ))
        args.append(mask)
    if has_rel:
        # heads dim sharded like q's heads dim (tensor axis)
        in_specs.append(P(None, AXIS_TENSOR))
        args.append(rel_bias_table)

    kw = dict(scale=scale, axis_name=AXIS_SEQ, causal=causal,
              rel=rel_bias_spec if has_rel else None)

    def fn(q_, k_, v_, *rest):
        rest = list(rest)
        m_ = rest.pop(0) if has_mask else None
        t_ = rest.pop(0) if has_rel else None
        return _ring_shard(q_, k_, v_, m_, t_, **kw)

    return jax.shard_map(
        fn, mesh=mesh, in_specs=tuple(in_specs), out_specs=qkv_spec,
        check_vma=False,
    )(*args)


def ring_attention_or_fallback(q, k, v, mask=None, scale=None,
                               causal: bool = False, rel_bias_table=None,
                               rel_bias_spec: tuple | None = None):
    """Model-facing ring dispatch: run ring attention when the ambient
    mesh (``parallel.mesh``) has an active ``seq`` axis and the shapes
    divide it; otherwise fall back to the numerics-identical XLA kernel
    (materializing the relative bias globally when one is requested).

    The fallback is principled, not a silent downgrade: ring attention is
    a *layout* choice (sequence sharding + ppermute schedule) over the
    same exact-softmax math, and the ambient mesh is absent exactly in
    the out-of-training traces (``model.init`` param init, single-device
    eval/export) where sequence sharding is meaningless.
    """
    from huggingface_sagemaker_tensorflow_distributed_tpu.ops.attention import (
        make_causal_mask,
        relative_position_bias,
        xla_attention,
    )
    from huggingface_sagemaker_tensorflow_distributed_tpu.parallel.mesh import (
        maybe_current_mesh,
    )

    def xla_path():
        full_mask = mask
        if rel_bias_table is not None:
            bidirectional, num_buckets, max_distance = rel_bias_spec
            bias = relative_position_bias(
                rel_bias_table, jnp.arange(q.shape[2])[:, None],
                jnp.arange(k.shape[2])[None, :], bidirectional=bidirectional,
                num_buckets=num_buckets, max_distance=max_distance)
            full_mask = bias if full_mask is None else full_mask + bias
        if causal:
            cm = make_causal_mask(q.shape[2], k.shape[2])
            full_mask = cm if full_mask is None else full_mask + cm
        return xla_attention(q, k, v, mask=full_mask, scale=scale)

    mesh = maybe_current_mesh()
    if mesh is None or mesh.shape.get(AXIS_SEQ, 1) <= 1:
        return xla_path()
    b, h, s, _ = q.shape
    dp = 1
    for ax in data_axis_names():
        dp *= mesh.shape.get(ax, 1)
    tp = mesh.shape.get(AXIS_TENSOR, 1)
    sp = mesh.shape[AXIS_SEQ]
    # general [b,h,q,k] masks have no ring form — only broadcastable
    # padding masks ride the ring (causal + relative bias are recomputed
    # per ring step instead)
    general_mask = mask is not None and (mask.shape[-2] != 1 or mask.shape[1] != 1)
    if general_mask or b % dp or h % tp or s % sp or k.shape[2] % sp:
        return xla_path()
    return ring_attention(q, k, v, mask=mask, scale=scale, mesh=mesh,
                          causal=causal, rel_bias_table=rel_bias_table,
                          rel_bias_spec=rel_bias_spec)
