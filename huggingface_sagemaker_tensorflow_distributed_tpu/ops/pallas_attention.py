"""Pallas flash attention for TPU: blocked online-softmax forward and a
fused backward, both O(seq) in memory.

TPU-native replacement for the attention CUDA kernels the reference gets
through TF (reference ``scripts/train.py:117``). The forward streams K/V
blocks through VMEM keeping only the running row-max/row-sum and the
output accumulator on chip (the logits tile for one (q-block, kv-block)
pair never touches HBM), and saves the per-row log-sum-exp so the
backward can recompute probabilities blockwise without materialising the
[S, S] attention matrix either — two fused kernels produce dQ and
dK/dV/dmask directly.

Numerics match ``ops.attention.xla_attention``: fp32 logits and softmax
statistics, probabilities cast to the value dtype for the PV matmul
(exactly what the XLA path does), output in the query dtype. Verified in
``tests/test_pallas_attention.py`` via interpret mode on CPU and compiled
on real TPU by the bench path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def _causal_mask_block(iq, ik, block_q, block_k, window=None):
    """Additive fp32 mask for the (iq, ik) tile of a causal attention;
    ``window`` additionally bands it (key within the last N positions —
    Mistral sliding window)."""
    q_pos = iq * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    k_pos = ik * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    keep = k_pos <= q_pos
    if window is not None:
        keep &= k_pos > q_pos - window
    return jnp.where(keep, 0.0, _NEG_INF).astype(jnp.float32)


def _tile_runs(causal, iq, ik, block_q, block_k, window=None):
    """Whether the (iq, ik) tile contributes: causal tiles strictly above
    the diagonal are skipped entirely, and with a sliding ``window``
    tiles entirely BELOW the band too — O(S·window) work at long S
    (shared by fwd / dQ / dKV kernels)."""
    if not causal:
        return True
    run = ik * block_k <= iq * block_q + block_q - 1
    if window is not None:
        # tile overlaps the band iff its newest key can still be seen by
        # its oldest query: k_max >= q_min - window + 1
        run &= (ik + 1) * block_k - 1 >= iq * block_q - window + 1
    return run


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, mask_ref, o_ref, lse_ref,
                acc_ref, m_ref, l_ref, *, scale, causal, block_q, block_k,
                window=None):
    """Grid (B, H, num_q, num_kv); kv is innermost so the online-softmax
    state in VMEM scratch carries across kv steps of one q block.
    ``lse_ref`` is None on the inference-only path (no residual needed)."""
    ik = pl.program_id(3)
    num_kv = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    iq = pl.program_id(2)
    # with causal masking, tiles strictly above the diagonal contribute 0
    run = _tile_runs(causal, iq, ik, block_q, block_k, window)

    @pl.when(run)
    def _step():
        q = q_ref[0, 0]                                   # [BQ, D]
        k = k_ref[0, 0]                                   # [BK, D]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # [BQ, BK] fp32
        if mask_ref is not None:
            s = s + mask_ref[0].astype(jnp.float32)       # [1, BK] broadcast
        if causal:
            s = s + _causal_mask_block(iq, ik, block_q, block_k, window)

        m_prev = m_ref[:, :1]                             # [BQ, 1]
        l_prev = l_ref[:, :1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)                            # [BQ, BK] fp32
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

        v = v_ref[0, 0]                                   # [BK, D]
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)           # [BQ, D] fp32
        acc_ref[...] = acc_ref[...] * alpha + pv

    @pl.when(ik == num_kv - 1)
    def _finish():
        l = l_ref[:, :1]
        # fully-masked rows have l == 0 only if every key hit -inf; the
        # additive padding mask uses -1e9 so l stays positive — guard anyway
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / safe_l).astype(o_ref.dtype)
        if lse_ref is not None:
            # TPU tiling wants a 128-lane trailing dim: store LSE broadcast
            # across lanes (the layout the backward kernels read back)
            lse_ref[0, 0] = jnp.broadcast_to(m_ref[:, :1] + jnp.log(safe_l),
                                             lse_ref.shape[2:])


@functools.partial(
    jax.jit,
    static_argnames=("scale", "block_q", "block_k", "causal", "interpret",
                     "want_lse", "window"))
def _flash_fwd_call(q, k, v, mask, scale, block_q, block_k, causal, interpret,
                    want_lse=True, window=None):
    batch, heads, q_len, head_dim = q.shape
    kv_len = k.shape[2]
    grid = (batch, heads, q_len // block_q, kv_len // block_k)

    in_specs = [
        pl.BlockSpec((1, 1, block_q, head_dim), lambda b, h, j, i: (b, h, j, 0)),
        pl.BlockSpec((1, 1, block_k, head_dim), lambda b, h, j, i: (b, h, i, 0)),
        pl.BlockSpec((1, 1, block_k, head_dim), lambda b, h, j, i: (b, h, i, 0)),
    ]
    args = [q, k, v]
    has_mask = mask is not None
    if has_mask:
        # additive [B,1,1,S] → [B,1,S]; blocked over kv
        args.append(mask.reshape(batch, 1, kv_len))
        in_specs.append(
            pl.BlockSpec((1, 1, block_k), lambda b, h, j, i: (b, 0, i)))

    def kernel(*refs):
        if has_mask and want_lse:
            q_, k_, v_, m_, o_, lse_, acc_, mx_, l_ = refs
        elif has_mask:
            q_, k_, v_, m_, o_, acc_, mx_, l_ = refs
            lse_ = None
        elif want_lse:
            q_, k_, v_, o_, lse_, acc_, mx_, l_ = refs
            m_ = None
        else:
            q_, k_, v_, o_, acc_, mx_, l_ = refs
            m_ = lse_ = None
        _fwd_kernel(q_, k_, v_, m_, o_, lse_, acc_, mx_, l_, scale=scale,
                    causal=causal, block_q=block_q, block_k=block_k,
                    window=window)

    out_specs = [
        pl.BlockSpec((1, 1, block_q, head_dim), lambda b, h, j, i: (b, h, j, 0)),
    ]
    out_shape = [jax.ShapeDtypeStruct((batch, heads, q_len, head_dim), q.dtype)]
    if want_lse:
        out_specs.append(
            pl.BlockSpec((1, 1, block_q, 128), lambda b, h, j, i: (b, h, j, 0)))
        out_shape.append(
            jax.ShapeDtypeStruct((batch, heads, q_len, 128), jnp.float32))

    outs = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((block_q, head_dim), jnp.float32),   # acc
            pltpu.VMEM((block_q, 128), jnp.float32),        # running max
            pltpu.VMEM((block_q, 128), jnp.float32),        # running sum
        ],
        interpret=interpret,
        name="flash_fwd",
    )(*args)
    return (outs[0], outs[1]) if want_lse else (outs[0], None)


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, o_ref, mask_ref,
               dq_ref, dq_acc, delta_ref, *, scale, causal, block_q, block_k,
               window=None):
    """Grid (B, H, num_q, num_kv); accumulates dQ for one q block across
    kv blocks.  dS = P ∘ (dO·Vᵀ − Δ), dQ = scale · dS·K.
    Δ_i = Σ_d dO_id·O_id is computed HERE (once per q block, into VMEM
    scratch) rather than by a separate XLA pass — the [B,H,S,128]
    lane-broadcast Δ array never exists in HBM."""
    ik = pl.program_id(3)
    num_kv = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)
        d = jnp.sum(do_ref[0, 0].astype(jnp.float32)
                    * o_ref[0, 0].astype(jnp.float32), axis=-1, keepdims=True)
        delta_ref[...] = jnp.broadcast_to(d, delta_ref.shape)

    iq = pl.program_id(2)
    run = _tile_runs(causal, iq, ik, block_q, block_k, window)

    @pl.when(run)
    def _step():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if mask_ref is not None:
            s = s + mask_ref[0].astype(jnp.float32)
        if causal:
            s = s + _causal_mask_block(iq, ik, block_q, block_k, window)
        lse = lse_ref[0, 0][:, :1]                        # [BQ, 1]
        p = jnp.exp(s - lse)                              # [BQ, BK] fp32

        do = do_ref[0, 0]
        v = v_ref[0, 0]
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)           # [BQ, BK]
        delta = delta_ref[:, :1]                          # [BQ, 1]
        ds = p * (dp - delta)                             # [BQ, BK] fp32
        dq_acc[...] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    @pl.when(ik == num_kv - 1)
    def _finish():
        dq_ref[0, 0] = dq_acc[...].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, o_ref, mask_ref,
                dk_ref, dv_ref, dmask_ref, dk_acc, dv_acc, dm_acc,
                *, scale, causal, block_q, block_k, window=None):
    """Grid (B, H, num_kv, num_q); accumulates dK/dV (and the padding-mask
    cotangent) for one kv block across q blocks.
    dV = Pᵀ·dO, dK = scale · dSᵀ·Q, dmask = Σ_q dS. Δ is recomputed
    per (kv, q) tile from the dO/O blocks already in VMEM — one
    elementwise [BQ, D] pass on the VPU instead of an HBM tile read."""
    iq = pl.program_id(3)
    num_q = pl.num_programs(3)

    @pl.when(iq == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)
        if dm_acc is not None:
            dm_acc[...] = jnp.zeros_like(dm_acc)

    ik = pl.program_id(2)
    run = _tile_runs(causal, iq, ik, block_q, block_k, window)

    @pl.when(run)
    def _step():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # [BQ, BK]
        if mask_ref is not None:
            s = s + mask_ref[0].astype(jnp.float32)
        if causal:
            s = s + _causal_mask_block(iq, ik, block_q, block_k, window)
        lse = lse_ref[0, 0][:, :1]
        p = jnp.exp(s - lse)                              # [BQ, BK]

        do = do_ref[0, 0]                                 # [BQ, D]
        # dV += Pᵀ · dO   (contract over q rows — no explicit transpose)
        dv_acc[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)           # [BK, D]

        v = v_ref[0, 0]
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)           # [BQ, BK]
        delta = jnp.sum(do.astype(jnp.float32)
                        * o_ref[0, 0].astype(jnp.float32),
                        axis=-1, keepdims=True)           # [BQ, 1]
        ds = p * (dp - delta)                             # [BQ, BK]
        dk_acc[...] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # [BK, D]
        if dm_acc is not None:
            dm_acc[...] += jnp.sum(ds, axis=0, keepdims=True)  # [1, BK]

    @pl.when(iq == num_q - 1)
    def _finish():
        dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)
        if dmask_ref is not None:
            dmask_ref[0, 0] = dm_acc[...]


@functools.partial(
    jax.jit,
    static_argnames=("scale", "block_q", "block_k", "causal", "interpret",
                     "window"))
def _flash_bwd_call(q, k, v, mask, o, lse, do, scale, block_q, block_k,
                    causal, interpret, window=None):
    batch, heads, q_len, head_dim = q.shape
    kv_len = k.shape[2]
    num_q = q_len // block_q
    num_kv = kv_len // block_k

    # Δ = Σ_d dO·O is folded into the kernels (dQ: once per q block into
    # scratch; dKV: recomputed per tile) — no HBM Δ array
    q_spec = pl.BlockSpec((1, 1, block_q, head_dim),
                          lambda b, h, j, i: (b, h, j, 0))
    kv_spec = pl.BlockSpec((1, 1, block_k, head_dim),
                           lambda b, h, j, i: (b, h, i, 0))
    row_spec = pl.BlockSpec((1, 1, block_q, 128), lambda b, h, j, i: (b, h, j, 0))
    base_args = [q, k, v, do, lse, o]
    base_specs = [q_spec, kv_spec, kv_spec, q_spec, row_spec, q_spec]
    has_mask = mask is not None
    if has_mask:
        base_args.append(mask.reshape(batch, 1, kv_len))
        base_specs.append(
            pl.BlockSpec((1, 1, block_k), lambda b, h, j, i: (b, 0, i)))

    kw = dict(scale=scale, causal=causal, block_q=block_q, block_k=block_k,
              window=window)

    def dq_kernel(*refs):
        if has_mask:
            (q_, k_, v_, do_, lse_, o_, m_, dq_, acc_, dlt_) = refs
        else:
            (q_, k_, v_, do_, lse_, o_, dq_, acc_, dlt_) = refs
            m_ = None
        _dq_kernel(q_, k_, v_, do_, lse_, o_, m_, dq_, acc_, dlt_, **kw)

    dq = pl.pallas_call(
        dq_kernel,
        grid=(batch, heads, num_q, num_kv),
        in_specs=base_specs,
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, head_dim), jnp.float32),
                        pltpu.VMEM((block_q, 128), jnp.float32)],  # Δ
        interpret=interpret,
        name="flash_bwd_dq",
    )(*base_args)

    # kv-major grid: (b, h, ik, iq) with q innermost
    q_spec_t = pl.BlockSpec((1, 1, block_q, head_dim),
                            lambda b, h, i, j: (b, h, j, 0))
    kv_spec_t = pl.BlockSpec((1, 1, block_k, head_dim),
                             lambda b, h, i, j: (b, h, i, 0))
    row_spec_t = pl.BlockSpec((1, 1, block_q, 128),
                              lambda b, h, i, j: (b, h, j, 0))
    specs_t = [q_spec_t, kv_spec_t, kv_spec_t, q_spec_t, row_spec_t, q_spec_t]
    if has_mask:
        specs_t.append(
            pl.BlockSpec((1, 1, block_k), lambda b, h, i, j: (b, 0, i)))

    out_specs = [kv_spec_t, kv_spec_t]
    out_shapes = [jax.ShapeDtypeStruct(k.shape, k.dtype),
                  jax.ShapeDtypeStruct(v.shape, v.dtype)]
    scratch = [pltpu.VMEM((block_k, head_dim), jnp.float32),
               pltpu.VMEM((block_k, head_dim), jnp.float32)]
    if has_mask:
        out_specs.append(
            pl.BlockSpec((1, 1, 1, block_k), lambda b, h, i, j: (b, h, 0, i)))
        out_shapes.append(
            jax.ShapeDtypeStruct((batch, heads, 1, kv_len), jnp.float32))
        scratch.append(pltpu.VMEM((1, block_k), jnp.float32))

    def dkv_kernel(*refs):
        if has_mask:
            (q_, k_, v_, do_, lse_, o_, m_, dk_, dv_, dm_,
             dka_, dva_, dma_) = refs
        else:
            (q_, k_, v_, do_, lse_, o_, dk_, dv_, dka_, dva_) = refs
            m_ = dm_ = dma_ = None
        _dkv_kernel(q_, k_, v_, do_, lse_, o_, m_, dk_, dv_, dm_,
                    dka_, dva_, dma_, **kw)

    outs = pl.pallas_call(
        dkv_kernel,
        grid=(batch, heads, num_kv, num_q),
        in_specs=specs_t,
        out_specs=out_specs,
        out_shape=out_shapes,
        scratch_shapes=scratch,
        interpret=interpret,
        name="flash_bwd_dkv",
    )(*base_args)

    if has_mask:
        dk, dv, dmask_bh = outs                    # [B, H, 1, S]
        # mask broadcasts over (heads, q): its cotangent sums those axes
        dmask = jnp.sum(dmask_bh, axis=1).reshape(batch, 1, 1, kv_len)
        dmask = dmask.astype(mask.dtype)
    else:
        dk, dv = outs
        dmask = None
    return dq, dk, dv, dmask


# ---------------------------------------------------------------------------
# public entry
# ---------------------------------------------------------------------------

def flash_attention(q, k, v, mask=None, scale=None, block_q: int = 512,
                    block_k: int = 512, causal: bool = False,
                    interpret: bool | None = None,
                    window: int | None = None):
    """Flash attention. q,k,v: [B, H, S, D]; mask additive, broadcastable
    to [B, 1, 1, S] (padding masks; [B,H,Q,K] masks fall back to XLA).

    Fully differentiable with fused Pallas backward kernels — no [S, S]
    residuals are ever stored (only the output and the per-row
    log-sum-exp), so it replaces attention rematerialisation too. The
    additive mask is itself a differentiable input (learned biases are
    valid); its cotangent is accumulated in the dK/dV kernel.
    """
    from huggingface_sagemaker_tensorflow_distributed_tpu.ops.attention import xla_attention

    if window is not None and not causal:
        raise ValueError("window requires causal=True (sliding-window "
                         "attention is an autoregressive construct)")
    head_dim = q.shape[-1]
    scale = scale if scale is not None else head_dim ** -0.5
    q_len, kv_len = q.shape[2], k.shape[2]
    block_q = min(block_q, q_len)
    block_k = min(block_k, kv_len)
    general_mask = mask is not None and (mask.shape[1] > 1 or mask.shape[2] > 1)
    if q_len % block_q != 0 or kv_len % block_k != 0 or general_mask:
        if causal:
            from huggingface_sagemaker_tensorflow_distributed_tpu.ops.attention import (
                make_banded_causal_mask,
                make_causal_mask,
            )
            cm = (make_banded_causal_mask(q_len, window, kv_len)
                  if window is not None else make_causal_mask(q_len, kv_len))
            mask = cm if mask is None else mask + cm
        return xla_attention(q, k, v, mask=mask, scale=scale)
    if interpret is None:
        interpret = jax.devices()[0].platform != "tpu"

    def kernel(q, k, v, mask):
        return _flash_vjp(q, k, v, mask, scale, block_q, block_k, causal,
                          interpret, window)

    return _shard_over_mesh(kernel, q.shape)(q, k, v, mask)


def _shard_over_mesh(kernel, q_shape):
    """Under an ambient mesh of more than one device (the Trainer's
    jitted steps, the TP serve engine), run ``kernel(q, k, v, mask)``
    per shard: a Mosaic kernel cannot be partitioned by GSPMD — jax
    refuses to lower one under a multi-device jit ("Mosaic kernels
    cannot be automatically partitioned. Please wrap the call in a
    shard_map") — so the batch is split over the data axes and, where
    the head count divides, the heads over ``tensor``, exactly the
    layout the surrounding matmuls already have. Same shape as the
    fused vocab-CE wrapping in ``train/trainer.py``."""
    from jax.sharding import PartitionSpec as P

    from huggingface_sagemaker_tensorflow_distributed_tpu.parallel.mesh import (
        AXIS_TENSOR,
        data_axis_names,
        maybe_current_mesh,
    )

    mesh = maybe_current_mesh()
    if mesh is None or mesh.size == 1:
        return kernel
    heads = (AXIS_TENSOR
             if q_shape[1] % mesh.shape.get(AXIS_TENSOR, 1) == 0 else None)
    qkv = P(data_axis_names(), heads, None, None)
    # check_vma=False: pallas_call does not annotate varying-mesh axes
    # on its outputs, which the default vma check rejects
    return jax.shard_map(
        kernel, mesh=mesh,
        in_specs=(qkv, qkv, qkv, P(data_axis_names(), None, None, None)),
        out_specs=qkv, check_vma=False)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _flash_vjp(q, k, v, mask, scale, block_q, block_k, causal, interpret,
               window):
    # inference-only path: skip the LSE residual output entirely
    out, _ = _flash_fwd_call(q, k, v, mask, scale, block_q, block_k, causal,
                             interpret, want_lse=False, window=window)
    return out


def _flash_vjp_fwd(q, k, v, mask, scale, block_q, block_k, causal, interpret,
                   window):
    out, lse = _flash_fwd_call(q, k, v, mask, scale, block_q, block_k, causal,
                               interpret, window=window)
    return out, (q, k, v, mask, out, lse)


def _flash_vjp_bwd(scale, block_q, block_k, causal, interpret, window,
                   res, g):
    q, k, v, mask, out, lse = res
    dq, dk, dv, dmask = _flash_bwd_call(
        q, k, v, mask, out, lse, g, scale, block_q, block_k, causal,
        interpret, window)
    return dq, dk, dv, dmask


_flash_vjp.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)
