"""Pallas fused LM-head + softmax cross-entropy, blocked over the vocab.

TPU-native replacement for the full-logits LM loss the reference's stack
computes via TF/Keras (reference ``scripts/train.py:118-119`` is the
seq-cls variant; the HF ecosystem it rides pairs every LM with a dense
head + CE). The standard formulation materialises ``logits = H·Wᵀ`` as a
[tokens, vocab] array in HBM (fp32/bf16, hundreds of MB at GPT-2 shapes)
purely to reduce it to one scalar per token. Here the head matmul and
the loss fuse: the forward streams vocab blocks of W through VMEM
keeping only the running row-max / row-sum-exp / label-logit / argmax
on chip (flash-attention's online softmax, applied to the vocab axis),
and the backward recomputes probabilities blockwise from the saved LSE —
producing dH and dW directly. The [tokens, vocab] matrix never exists.

Numerics: logits and softmax statistics in fp32 (matmuls run on the MXU
with ``preferred_element_type=f32``), matching
``optax.softmax_cross_entropy_with_integer_labels`` to fp32 roundoff.
Verified against the unfused path in ``tests/test_vocab_ce.py``
(interpret mode on CPU; compiled on TPU by the bench path).

Weights may be vocab-padded (TPU lane alignment): logits for rows
``>= vocab_size`` are forced to -inf so padding never leaks into the
loss, predictions, or gradients.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def _vocab_ids(iv, block_v, block_n):
    """[BN, BV] int32 grid of global vocab ids for the (·, iv) tile."""
    return iv * block_v + jax.lax.broadcasted_iota(
        jnp.int32, (block_n, block_v), 1)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(h_ref, w_ref, label_ref, loss_ref, lse_ref, pred_ref,
                m_ref, l_ref, ll_ref, ix_ref, zs_ref=None, *, vocab_size,
                block_n, block_v, epsilon=0.0):
    """Grid (num_n, num_v), v innermost: online softmax stats over vocab
    blocks for one token block. Tracks running max ``m``, sum-exp ``l``,
    the label's logit ``ll`` and the argmax id ``ix`` in VMEM scratch.
    With ``epsilon`` > 0 (uniform label smoothing) a running logit SUM
    ``zs`` rides along and the emitted loss becomes
    ``lse - (1-eps)*z_label - eps*mean(z)`` — the smoothed CE, still
    with no [N, V] materialization."""
    iv = pl.program_id(1)
    num_v = pl.num_programs(1)

    @pl.when(iv == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        ll_ref[...] = jnp.full_like(ll_ref, _NEG_INF)
        ix_ref[...] = jnp.zeros_like(ix_ref)
        if epsilon > 0:
            zs_ref[...] = jnp.zeros_like(zs_ref)

    h = h_ref[...]                                        # [BN, H]
    w = w_ref[...]                                        # [BV, H]
    s = jax.lax.dot_general(
        h, w, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)               # [BN, BV] fp32
    vids = _vocab_ids(iv, block_v, block_n)
    s = jnp.where(vids < vocab_size, s, _NEG_INF)         # mask vocab padding

    labels = label_ref[...][:, :1]                        # [BN, 1]
    hit = vids == labels                                  # [BN, BV]
    ll_blk = jnp.max(jnp.where(hit, s, _NEG_INF), axis=-1, keepdims=True)
    ll_ref[...] = jnp.maximum(ll_ref[...], jnp.broadcast_to(ll_blk, ll_ref.shape))

    m_prev = m_ref[:, :1]                                 # [BN, 1]
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    # strict > keeps the FIRST maximal id across blocks (jnp.argmax parity)
    better = m_cur > m_prev
    ix_blk = iv * block_v + jnp.argmax(s, axis=-1)[:, None]  # [BN, 1] int32
    ix_ref[...] = jnp.where(jnp.broadcast_to(better, ix_ref.shape),
                            jnp.broadcast_to(ix_blk, ix_ref.shape),
                            ix_ref[...])
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)
    l_new = alpha * l_ref[:, :1] + jnp.sum(jnp.exp(s - m_new), axis=-1,
                                           keepdims=True)
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)
    if epsilon > 0:
        zs_blk = jnp.sum(jnp.where(vids < vocab_size, s, 0.0), axis=-1,
                         keepdims=True)
        zs_ref[...] = zs_ref[...] + jnp.broadcast_to(zs_blk, zs_ref.shape)

    @pl.when(iv == num_v - 1)
    def _finish():
        lse = m_ref[:, :1] + jnp.log(l_ref[:, :1])        # [BN, 1]
        lse_ref[...] = jnp.broadcast_to(lse, lse_ref.shape)
        if epsilon > 0:
            target = ((1.0 - epsilon) * ll_ref[:, :1]
                      + epsilon * zs_ref[:, :1] / vocab_size)
        else:
            target = ll_ref[:, :1]
        loss_ref[...] = jnp.broadcast_to(lse - target, loss_ref.shape)
        pred_ref[...] = ix_ref[...]


@functools.partial(
    jax.jit, static_argnames=("vocab_size", "block_n", "block_v",
                              "interpret", "epsilon"))
def _fused_ce_fwd_call(hidden, weight, labels, vocab_size, block_n, block_v,
                       interpret, epsilon=0.0):
    n_tok, h_dim = hidden.shape
    v_pad = weight.shape[0]
    grid = (n_tok // block_n, v_pad // block_v)

    # labels ride in lane-broadcast [N, 128] form (TPU row-vector layout)
    lab = jnp.broadcast_to(labels.astype(jnp.int32)[:, None], (n_tok, 128))

    scratch = [
        pltpu.VMEM((block_n, 128), jnp.float32),   # running max
        pltpu.VMEM((block_n, 128), jnp.float32),   # running sum-exp
        pltpu.VMEM((block_n, 128), jnp.float32),   # label logit
        pltpu.VMEM((block_n, 128), jnp.int32),     # argmax id
    ]
    if epsilon > 0:
        scratch.append(pltpu.VMEM((block_n, 128), jnp.float32))  # logit sum
    outs = pl.pallas_call(
        functools.partial(_fwd_kernel, vocab_size=vocab_size,
                          block_n=block_n, block_v=block_v,
                          epsilon=epsilon),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, h_dim), lambda j, i: (j, 0)),
            pl.BlockSpec((block_v, h_dim), lambda j, i: (i, 0)),
            pl.BlockSpec((block_n, 128), lambda j, i: (j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_n, 128), lambda j, i: (j, 0)),
            pl.BlockSpec((block_n, 128), lambda j, i: (j, 0)),
            pl.BlockSpec((block_n, 128), lambda j, i: (j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_tok, 128), jnp.float32),   # loss
            jax.ShapeDtypeStruct((n_tok, 128), jnp.float32),   # lse
            jax.ShapeDtypeStruct((n_tok, 128), jnp.int32),     # pred
        ],
        scratch_shapes=scratch,
        interpret=interpret,
        name="vocab_ce_fwd",
    )(hidden, weight, lab)
    loss, lse, pred = outs
    return loss[:, 0], lse[:, 0], pred[:, 0]


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _dh_kernel(h_ref, w_ref, label_ref, lse_ref, g_ref, dh_ref, dh_acc,
               *, vocab_size, block_n, block_v, epsilon=0.0):
    """Grid (num_n, num_v): dH = Σ_v g ∘ (softmax − target) · W, where
    target is the (possibly smoothed) label distribution."""
    iv = pl.program_id(1)
    num_v = pl.num_programs(1)

    @pl.when(iv == 0)
    def _init():
        dh_acc[...] = jnp.zeros_like(dh_acc)

    h = h_ref[...]
    w = w_ref[...]
    s = jax.lax.dot_general(
        h, w, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    vids = _vocab_ids(iv, block_v, block_n)
    s = jnp.where(vids < vocab_size, s, _NEG_INF)
    p = jnp.exp(s - lse_ref[...][:, :1])                  # [BN, BV]
    onehot = (vids == label_ref[...][:, :1]).astype(jnp.float32)
    if epsilon > 0:
        target = ((1.0 - epsilon) * onehot
                  + epsilon / vocab_size
                  * (vids < vocab_size).astype(jnp.float32))
    else:
        target = onehot
    ds = (p - target) * g_ref[...][:, :1]                 # [BN, BV]
    dh_acc[...] += jax.lax.dot_general(
        ds.astype(w.dtype), w, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)               # [BN, H]

    @pl.when(iv == num_v - 1)
    def _finish():
        dh_ref[...] = dh_acc[...].astype(dh_ref.dtype)


def _dw_kernel(h_ref, w_ref, label_ref, lse_ref, g_ref, dw_ref, dw_acc,
               *, vocab_size, block_n, block_v, epsilon=0.0):
    """Grid (num_v, num_n), n innermost: dW = Σ_n (g ∘ (softmax − target))ᵀ · H."""
    i_n = pl.program_id(1)
    num_n = pl.num_programs(1)

    @pl.when(i_n == 0)
    def _init():
        dw_acc[...] = jnp.zeros_like(dw_acc)

    iv = pl.program_id(0)
    h = h_ref[...]
    w = w_ref[...]
    s = jax.lax.dot_general(
        h, w, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    vids = _vocab_ids(iv, block_v, block_n)
    s = jnp.where(vids < vocab_size, s, _NEG_INF)
    p = jnp.exp(s - lse_ref[...][:, :1])
    onehot = (vids == label_ref[...][:, :1]).astype(jnp.float32)
    if epsilon > 0:
        target = ((1.0 - epsilon) * onehot
                  + epsilon / vocab_size
                  * (vids < vocab_size).astype(jnp.float32))
    else:
        target = onehot
    ds = (p - target) * g_ref[...][:, :1]                 # [BN, BV]
    # contract over tokens: [BV, BN] · [BN, H] without explicit transpose
    dw_acc[...] += jax.lax.dot_general(
        ds.astype(h.dtype), h, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)               # [BV, H]

    @pl.when(i_n == num_n - 1)
    def _finish():
        dw_ref[...] = dw_acc[...].astype(dw_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("vocab_size", "block_n", "block_v",
                              "interpret", "epsilon"))
def _fused_ce_bwd_call(hidden, weight, labels, lse, g, vocab_size,
                       block_n, block_v, interpret, epsilon=0.0):
    n_tok, h_dim = hidden.shape
    v_pad = weight.shape[0]
    num_n = n_tok // block_n
    num_v = v_pad // block_v

    lab = jnp.broadcast_to(labels.astype(jnp.int32)[:, None], (n_tok, 128))
    lse_b = jnp.broadcast_to(lse[:, None], (n_tok, 128))
    g_b = jnp.broadcast_to(g.astype(jnp.float32)[:, None], (n_tok, 128))

    kw = dict(vocab_size=vocab_size, block_n=block_n, block_v=block_v,
              epsilon=epsilon)
    row = lambda j, i: (j, 0)                     # noqa: E731
    dh = pl.pallas_call(
        functools.partial(_dh_kernel, **kw),
        grid=(num_n, num_v),
        in_specs=[
            pl.BlockSpec((block_n, h_dim), row),
            pl.BlockSpec((block_v, h_dim), lambda j, i: (i, 0)),
            pl.BlockSpec((block_n, 128), row),
            pl.BlockSpec((block_n, 128), row),
            pl.BlockSpec((block_n, 128), row),
        ],
        out_specs=pl.BlockSpec((block_n, h_dim), row),
        out_shape=jax.ShapeDtypeStruct(hidden.shape, hidden.dtype),
        scratch_shapes=[pltpu.VMEM((block_n, h_dim), jnp.float32)],
        interpret=interpret,
        name="vocab_ce_bwd_dh",
    )(hidden, weight, lab, lse_b, g_b)

    # v-major grid, n innermost
    rown = lambda i, j: (j, 0)                    # noqa: E731
    dw = pl.pallas_call(
        functools.partial(_dw_kernel, **kw),
        grid=(num_v, num_n),
        in_specs=[
            pl.BlockSpec((block_n, h_dim), rown),
            pl.BlockSpec((block_v, h_dim), lambda i, j: (i, 0)),
            pl.BlockSpec((block_n, 128), rown),
            pl.BlockSpec((block_n, 128), rown),
            pl.BlockSpec((block_n, 128), rown),
        ],
        out_specs=pl.BlockSpec((block_v, h_dim), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(weight.shape, weight.dtype),
        scratch_shapes=[pltpu.VMEM((block_v, h_dim), jnp.float32)],
        interpret=interpret,
        name="vocab_ce_bwd_dw",
    )(hidden, weight, lab, lse_b, g_b)
    return dh, dw


# ---------------------------------------------------------------------------
# public entry
# ---------------------------------------------------------------------------

def fused_vocab_cross_entropy(hidden, weight, labels, block_n: int = 256,
                              block_v: int = 512,
                              interpret: bool | None = None,
                              label_smoothing: float = 0.0):
    """Per-token CE loss + argmax prediction of ``logits = hidden·weightᵀ``
    without materialising the logits.

    hidden: [N, H] (flattened tokens); weight: [V, H] (the tied embedding
    / LM head); labels: [N] int. Returns ``(loss [N] fp32, pred [N] int32)``.
    Differentiable in ``hidden`` and ``weight`` (fused backward kernels);
    ``pred`` carries no gradient. Masking of invalid tokens stays with the
    caller (multiply the returned loss by the validity mask), matching the
    unfused loss-function contract in ``train/trainer.py``.

    Falls back to the unfused XLA path off-TPU (``interpret=True`` forces
    the interpret-mode kernel there — tests; ``interpret=False`` off-TPU
    also falls back, since compiled Mosaic cannot build without a TPU)
    and for shapes that don't tile (N not a multiple of an 8-aligned
    block_n, or H not lane-aligned). The vocab axis always
    tiles: W is zero-padded up to a block_v multiple and padded rows are
    masked to -inf in-kernel."""
    from huggingface_sagemaker_tensorflow_distributed_tpu.ops.losses import (
        softmax_cross_entropy_with_integer_labels,
    )

    n_tok, h_dim = hidden.shape
    vocab_size = weight.shape[0]
    on_tpu = jax.devices()[0].platform == "tpu"
    if interpret is None:
        # off-TPU the kernel would run in interpret emulation — orders of
        # magnitude slower than the plain matmul; use the unfused path
        interpret = False if on_tpu else None
    elif interpret is False and not on_tpu:
        # compiled Mosaic (pltpu.VMEM scratch) cannot build off-TPU; treat a
        # forced interpret=False like the default off-TPU case: unfused path
        interpret = None
    # fp32 TPU tiles are (8, 128): block_n must stay 8-aligned
    block_n = min(block_n, n_tok) & ~7
    if (interpret is None or block_n == 0 or n_tok % block_n
            or h_dim % 128):
        logits = (hidden.astype(jnp.float32)
                  @ weight.astype(jnp.float32).T)
        per_tok = softmax_cross_entropy_with_integer_labels(logits, labels)
        if label_smoothing > 0:
            lse = jax.scipy.special.logsumexp(logits, axis=-1)
            uniform = lse - jnp.mean(logits, axis=-1)
            per_tok = ((1.0 - label_smoothing) * per_tok
                       + label_smoothing * uniform)
        return per_tok, jnp.argmax(logits, -1).astype(jnp.int32)
    v_pad = -(-vocab_size // block_v) * block_v
    if v_pad != vocab_size:
        weight = jnp.pad(weight, ((0, v_pad - vocab_size), (0, 0)))
    return _fused_ce_vjp(hidden, weight, labels, vocab_size, block_n,
                         block_v, interpret, float(label_smoothing))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _fused_ce_vjp(hidden, weight, labels, vocab_size, block_n, block_v,
                  interpret, epsilon):
    loss, _, pred = _fused_ce_fwd_call(hidden, weight, labels, vocab_size,
                                       block_n, block_v, interpret,
                                       epsilon=epsilon)
    return loss, pred


def _fused_ce_vjp_fwd(hidden, weight, labels, vocab_size, block_n, block_v,
                      interpret, epsilon):
    loss, lse, pred = _fused_ce_fwd_call(hidden, weight, labels, vocab_size,
                                         block_n, block_v, interpret,
                                         epsilon=epsilon)
    return (loss, pred), (hidden, weight, labels, lse)


def _fused_ce_vjp_bwd(vocab_size, block_n, block_v, interpret, epsilon,
                      res, g):
    hidden, weight, labels, lse = res
    g_loss, _ = g                                 # pred cotangent is float0
    # dw matches the (possibly vocab-padded) weight this vjp received;
    # the outer jnp.pad's transpose rule slices padding back off. Pad
    # rows get zero grad by construction (logit -inf ⇒ p = 0, and the
    # smoothed target's uniform mass is masked to real vocab rows).
    dh, dw = _fused_ce_bwd_call(hidden, weight, labels, lse, g_loss,
                                vocab_size, block_n, block_v, interpret,
                                epsilon=epsilon)
    return dh, dw, None


_fused_ce_vjp.defvjp(_fused_ce_vjp_fwd, _fused_ce_vjp_bwd)
