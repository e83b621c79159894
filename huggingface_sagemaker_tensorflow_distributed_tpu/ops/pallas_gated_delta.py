"""Pallas kernel for the gated delta rule's ONE-TOKEN step
(``ops/gated_delta.py::gated_delta_step``): a slot's recurrent state is
fetched once, updated in fast memory and written back to where it lay.

The jnp form is two XLA fusions over the state (its projections on ``k``
and ``q``, then decay and rank-one update): two reads and one write a
layer a step, 9.9 ms of ``olmo-hybrid-7b-pp2-gen-sat``'s 25.2 ms decode
step at 64 slots (ledger, PR 35). And the pool ``f32[64, 30, 96, 192]``
it ran over is laid out in ``(8, 128)`` tiles: 192 lanes are a tile and a
half, padded to 256 in HBM, so each of those passes moved 4/3 of the
bytes the shape counts (rehearsal compile for the v5e, PR 36). Both
halves of the excess are this module's to remove, because a kernel is
written against a tile shape:

- **the kernel owns the shape of what it carries.** :func:`state_layout`
  gives a recurrent layer's state row as the model declares it, a pure
  function of ``(H, dk, dv)``, the same on every backend: where ``G =
  128 / gcd(dv, 128)`` heads side by side fill whole lane tiles and ``G``
  divides ``H``, ``[H/G, dk, G*dv]`` (``[15, 96, 384]`` for 30 heads of
  ``96 x 192``: three lane tiles, twelve sublane tiles, no padding);
  otherwise ``[H, dk, dv]`` as it always was. :func:`pack` / :func:`unpack`
  go between that and the ``[B, H, dk, dv]`` the mathematics is written
  in (``gated_delta_chunked`` and the jnp step keep it);
- **one read, one write.** The grid walks slots x blocks of packed rows;
  a block is updated in VMEM and written through
  ``input_output_aliases`` onto the block it came from (the decode
  program donates the pools: no second copy of a pool appears). Inside a
  block, row by row, all float32 on the VPU (``[2, dk] x [dk, dv]`` on
  the MXU at ``Precision.HIGHEST`` would be six passes of an 8-row
  matmul a head: weight loads, not work)::

      pk, pq = sum_dk(S * K), sum_dk(S * Q)         (sublane reductions)
      u  = b (v - a pk)
      S  <- a S + K u          o = a pq + (k . q) u

  ``K`` / ``Q`` are the heads' ``k`` / ``q`` columns broadcast along the
  lanes (for packed heads: head ``G j + i`` over lanes ``[i dv, (i+1)
  dv)``), so ``k`` and ``q`` come TRANSPOSED, ``[B, dk, 2H]`` (3 MB at
  the cell's shape; a ``[..., dk, 1]`` operand would pad every value to
  a lane tile, a third of the state's own bytes). ``a = exp(g)``, ``b``,
  ``v`` and ``k . q`` come as lane rows of the packed shape, made by one
  small XLA fusion in front.

A masked row (``mask`` False: an inactive slot) has ``a = 1``, ``b = 0``:
its state comes out bit for bit as it went in.

:func:`state_step` says which form a process runs (``kernel`` on a TPU
for shapes whose packed row is whole tiles, ``xla`` otherwise): the
model's rule, written on every ``serve/decode_step`` span.
``tests/test_gated_delta_kernel.py`` holds the kernel to the jnp step in
interpret mode, ``tests/test_pallas_latent_attention.py`` compiles it for
the v5e at the cell's shape, ``chipbench/tools/state_step_microbench.py``
times both forms and both pool shapes on the chip.

A RUN of tokens (a prefill chunk) has a kernel of its own,
:func:`gated_delta_chunked_packed`: the chunked form of
``gated_delta_chunked`` in one pass a chunk. The grid walks rows x
packed rows x chunks of 64; a packed row's ``S`` (``[dk, G*dv]``, as
carried) stays in fast memory across its row's chunks and is read once
and written once. A chunk's ``G`` heads are STACKED on the sublanes
(``[G*64, ...]`` operands), so that every product of the WY transform is
one block-diagonal matmul over the packed row, all float32 at
``Precision.HIGHEST`` as the XLA form's::

    A = tril(K_b K^T * D, -1)          T = (I + A)^-1   (doubling: exact)
    W = T (K_b exp(gc))                U = T V_b
    o = (Q exp(gc)) S + tril(Q K^T * D) (U - W S)
    S <- exp(g_chunk) S + (K exp(g_after))^T (U - W S)

Every log-decay is a sum of ``g`` over the tokens it spans, taken
directly (``D``'s by one masked product): the XLA form's differences of
two running sums cancel their rounding against each other, which made it
the less accurate of the two against float64 (``benchmarks/
tpu_kernel_parity.py``). ``q`` / ``k`` come head-major and ``k``
transposed a chunk (XLA transposes in front, fused with the pad to whole
chunks), ``g`` and ``beta`` as lane rows a chunk; ``v`` and ``o`` keep
``[B, T, H*dv]``, whose ``G dv`` lanes a packed row are whole tiles.
:func:`state_chunk` is its rule (the same as :func:`state_step`'s),
written on every ``serve/prefill_chunk`` span; its gradient recomputes
through the XLA form (:func:`gated_delta_chunked_kernel`).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from huggingface_sagemaker_tensorflow_distributed_tpu.ops.gated_delta import (
    CHUNK,
    _masked,
    gated_delta_chunked,
    gated_delta_step,
)

STATE_STEPS = ("kernel", "xla")
_LANES, _SUBLANES = 128, 8
# a block's bytes: in and out, double-buffered, four of them live beside
# the step's temporaries in the 16 MiB the v5e gives a kernel
_BLOCK_BYTES = 1 << 20


def heads_per_row(H: int, dv: int) -> int:
    """``G``: the heads that lie side by side in one packed row. The
    fewest whose values fill whole lane tiles, where they divide ``H``;
    1 otherwise (and where ``dv`` is whole tiles already)."""
    G = _LANES // math.gcd(dv, _LANES)
    return G if H % G == 0 else 1


def state_layout(H: int, dk: int, dv: int) -> tuple:
    """A recurrent layer's state row (without the row axis): ``(H/G, dk,
    G*dv)``. A pure function of the shapes, the same on every backend."""
    G = heads_per_row(H, dv)
    return (H // G, dk, G * dv)


def pack(state):
    """``[B, H, dk, dv]`` -> ``[B, *state_layout(H, dk, dv)]``."""
    B, H, dk, dv = state.shape
    G = heads_per_row(H, dv)
    if G == 1:
        return state
    return jnp.swapaxes(state.reshape(B, H // G, G, dk, dv), 2, 3).reshape(
        B, H // G, dk, G * dv)


def unpack(packed, H: int):
    """``[B, H/G, dk, G*dv]`` -> ``[B, H, dk, dv]``: :func:`pack`'s
    inverse."""
    B, P, dk, L = packed.shape
    G = H // P
    if G == 1:
        return packed
    return jnp.swapaxes(packed.reshape(B, P, dk, G, L // G), 2, 3).reshape(
        B, H, dk, L // G)


def takes(H: int, dk: int, dv: int) -> bool:
    """Whether the compiled kernel has blocks for these shapes: the
    packed row's two minor dimensions are whole ``(8, 128)`` tiles."""
    _, rows, lanes = state_layout(H, dk, dv)
    return rows % _SUBLANES == 0 and lanes % _LANES == 0


def state_step(H: int, dk: int, dv: int, *, platform: str) -> str:
    """``kernel`` | ``xla``: the form a one-token step runs. A pure
    function of what the code can see, and nothing a user sets."""
    return "kernel" if platform == "tpu" and takes(H, dk, dv) else "xla"


def block_rows(P: int, dk: int, L: int) -> int:
    """Packed rows a grid step updates: the largest divisor of ``P``
    whose block stays under :data:`_BLOCK_BYTES`."""
    fit = [r for r in range(1, P + 1)
           if P % r == 0 and r * dk * L * 4 <= _BLOCK_BYTES]
    return max(fit, default=1)


def _step_kernel(kq_ref, rows_ref, s_ref, o_ref, s_out_ref, *, heads):
    """One slot's block of packed rows. ``kq_ref`` ``[1, dk, 2H]`` (``k``
    of every head, then ``q``), ``rows_ref`` ``[1, 4, P, L]`` (``a | b |
    v | k.q``, lane rows), ``s_ref`` / ``s_out_ref`` ``[1, block, dk, L]``
    (the same memory), ``o_ref`` ``[1, P, L]`` (the slot's, written a
    block's rows at a time)."""
    _, block, dk, L = s_ref.shape
    P = rows_ref.shape[2]
    G = heads // P
    dv = L // G
    kq = kq_ref[0]                                           # [dk, 2H]
    lane = lax.broadcasted_iota(jnp.int32, (dk, L), 1)

    def columns(first):
        """Heads ``first .. first + G`` of ``kq``'s columns, each over
        its own ``dv`` lanes (one head: a ``[dk, 1]`` column as it is)."""
        out = kq[:, first:first + 1]
        for i in range(1, G):
            out = jnp.where(lane < i * dv, out,
                            kq[:, first + i:first + i + 1])
        return out

    def rows_of(c):
        for r in range(block):
            p = c * block + r
            S = s_ref[0, r]                                  # [dk, L]
            K, Q = columns(p * G), columns(heads + p * G)
            a, b, v, kd = (rows_ref[0, j, p:p + 1] for j in range(4))
            pk = jnp.sum(S * K, axis=0, keepdims=True)       # [1, L]
            pq = jnp.sum(S * Q, axis=0, keepdims=True)
            u = b * (v - a * pk)
            s_out_ref[0, r] = a * S + K * u
            o_ref[0, p:p + 1] = a * pq + kd * u

    # a branch a block of the slot: its rows' numbers are then static, and
    # a head's column of kq a static lane
    for c in range(P // block):
        pl.when(pl.program_id(1) == c)(functools.partial(rows_of, c))


@functools.partial(jax.jit, static_argnames=("heads", "block", "interpret"))
def _call(kq, rows, state, heads, block, interpret):
    B, P, dk, L = state.shape
    return pl.pallas_call(
        functools.partial(_step_kernel, heads=heads),
        grid=(B, P // block),
        in_specs=[
            pl.BlockSpec((1, dk, 2 * heads), lambda b, c: (b, 0, 0)),
            pl.BlockSpec((1, 4, P, L), lambda b, c: (b, 0, 0, 0)),
            pl.BlockSpec((1, block, dk, L), lambda b, c: (b, c, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, P, L), lambda b, c: (b, 0, 0)),
            pl.BlockSpec((1, block, dk, L), lambda b, c: (b, c, 0, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((B, P, L), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, jnp.float32)],
        input_output_aliases={2: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=7 * state.size, transcendentals=0,
            bytes_accessed=8 * state.size),
        name="gated_delta_step",
        interpret=interpret,
    )(kq, rows, state)


def gated_delta_step_packed(q, k, v, g, beta, state, mask=None, *,
                            block: int | None = None,
                            interpret: bool | None = None):
    """:func:`~.gated_delta.gated_delta_step` on a PACKED state: ``q``,
    ``k`` ``[B, H, dk]``, ``v`` ``[B, H, dv]``, ``g``, ``beta`` ``[B,
    H]``, ``state`` ``[B, H/G, dk, G*dv]`` float32 (any ``G`` that divides
    ``H``: :func:`state_layout`'s, or 1 for a state left ``[B, H, dk,
    dv]``), ``mask`` ``[B]`` bool. Returns ``(o [B, H, dv] float32,
    state)``, the state in the operand's own memory where the caller
    donates it. ``block`` (packed rows a grid step; :func:`block_rows` by
    default) divides ``H/G``. Interpreted off a TPU unless ``interpret``
    says."""
    B, H, dk = k.shape
    dv = v.shape[-1]
    P, L = state.shape[1], state.shape[-1]
    if (state.dtype != jnp.float32 or H % P
            or state.shape != (B, P, dk, H // P * dv)):
        raise ValueError(
            f"state {state.dtype}{list(state.shape)} is no packing of "
            f"float32 {[B, H, dk, dv]}")
    if block is None:
        block = block_rows(P, dk, L)
    if P % block:
        raise ValueError(f"block {block} does not divide {P} packed rows")
    if interpret is None:
        interpret = jax.devices()[0].platform != "tpu"
    g, beta = _masked(g, beta, mask)
    q32, k32, v32 = (a.astype(jnp.float32) for a in (q, k, v))
    kq = jnp.swapaxes(jnp.concatenate([k32, q32], axis=1), 1, 2)

    def lanes(a):            # [B, H] a head -> [B, P, L], dv lanes a head
        return jnp.broadcast_to(a[..., None], (B, H, dv)).reshape(B, P, L)

    rows = jnp.stack([lanes(jnp.exp(g)), lanes(beta), v32.reshape(B, P, L),
                      lanes(jnp.sum(k32 * q32, axis=-1))], axis=1)
    o, state = _call(kq, rows, state, H, block, interpret)
    return o.reshape(B, H, dv), state


def gated_delta_step_carried(q, k, v, g, beta, state, mask=None, *,
                             form: str):
    """One token a row on the state AS IT IS CARRIED
    (``[B, *state_layout(H, dk, dv)]``), by ``form`` (:func:`state_step`'s
    answer): the kernel, or the jnp step through an :func:`unpack` and a
    :func:`pack` (the identity where the layout is ``[H, dk, dv]``)."""
    if form == "kernel":
        return gated_delta_step_packed(q, k, v, g, beta, state, mask)
    H = k.shape[1]
    o, state = gated_delta_step(q, k, v, g, beta, unpack(state, H), mask)
    return o, pack(state)


# -- a run of tokens: the chunked form in one pass a chunk ----------------------

def state_chunk(H: int, dk: int, dv: int, *, platform: str) -> str:
    """``kernel`` | ``xla``: the form a run of tokens (a prefill chunk)
    runs: :func:`state_step`'s rule, for the chunk kernel."""
    return state_step(H, dk, dv, platform=platform)


def _dot(a, b, contract=((1,), (0,))):
    return lax.dot_general(a, b, (contract, ((), ())),
                           precision=lax.Precision.HIGHEST,
                           preferred_element_type=jnp.float32)


def _stack(x, width: int, at, heads: int):
    """``[C, n]`` -> ``[heads C, n]``: the columns ``[i width, (i+1)
    width)`` of ``x`` (``at`` their index) on rows ``[i C, (i+1) C)``,
    zeros elsewhere."""
    return jnp.concatenate(
        [jnp.where((at >= i * width) & (at < (i + 1) * width), x, 0.0)
         for i in range(heads)], axis=0)


def _unstack(x, width: int, at, heads: int):
    """``[heads C, n]`` -> ``[C, n]``: :func:`_stack`'s inverse, each
    column from the rows of its own head."""
    C = x.shape[0] // heads
    out = x[0:C]
    for i in range(1, heads):
        out = jnp.where(at >= i * width, x[i * C:(i + 1) * C], out)
    return out


def _unit_lower_inverse(a, chunk: int, heads: int):
    """``(I + a)^-1`` for ``a`` ``[R, R]`` (``R = heads chunk``) strictly
    lower triangular inside each ``chunk x chunk`` diagonal block and zero
    outside them, SIDE BY SIDE (``[chunk, R]``: head ``i``'s block in
    columns ``[i chunk, (i+1) chunk)``), by doubling the inverted blocks:
    with ``T`` the inverse of the blocks of ``size``, that of the blocks of
    ``2 size`` is ``T - T M T``, ``M`` the lower-left quarters of ``a``'s
    ``2 size`` blocks (exact: the inverse of ``[[L1, 0], [L21, L2]]`` is
    ``[[T1, 0], [-T2 L21 T1, T2]]``). A block-diagonal left operand side
    by side against a right one stacked makes the same product in
    ``chunk`` rows instead of ``R``."""
    R = a.shape[0]
    ri = lax.broadcasted_iota(jnp.int32, (R, R), 0)
    ci = lax.broadcasted_iota(jnp.int32, (R, R), 1)
    col = lax.broadcasted_iota(jnp.int32, (1, R), 1)
    t = _unstack(jnp.where(ri == ci, 1.0, 0.0), chunk, col, heads)
    shift = 0
    while (1 << shift) < chunk:
        quarter = (((ri >> (shift + 1)) == (ci >> (shift + 1)))
                   & (((ri >> shift) & 1) == 1) & (((ci >> shift) & 1) == 0))
        tm = _dot(t, jnp.where(quarter, a, 0.0))
        t = t - _dot(tm, _stack(t, chunk, col, heads))
        shift += 1
    return t


def _chunk_kernel(q_ref, k_ref, kt_ref, v_ref, gb_ref, s_ref, o_ref,
                  s_out_ref):
    """One chunk of one packed row of one batch row. ``q_ref`` / ``k_ref``
    ``[1, G, C, dk]`` (the row's ``G`` heads, head-major), ``kt_ref`` ``[1,
    1, 1, dk, G C]`` (``k`` transposed, head ``i``'s tokens in lanes ``[i
    C, (i+1) C)``), ``v_ref`` / ``o_ref`` ``[1, C, L]`` (lanes as the
    packed row has them: head ``i`` over ``[i dv, (i+1) dv)``), ``gb_ref``
    ``[1, 1, 1, 2, G C]`` (the chunk's ``g`` and ``beta`` in ``kt``'s
    lanes), ``s_ref`` / ``s_out_ref`` ``[1, 1, dk, L]`` (the same memory;
    the output block stays in fast memory across the chunks of a row, and
    carries ``S``).

    Inside, a head's per-token quantities are STACKED: rows ``[i C, (i+1)
    C)`` of an ``[R = G C, ...]`` operand are head ``i``'s, so that every
    head of the row is one operand and its ``[R, R]`` products are
    block-diagonal (the masks keep a head to its own block); such a
    product as a LEFT operand is taken side by side (``[C, R]``). Products
    that share a right operand are made as one."""
    _, G, C, dk = q_ref.shape
    L = v_ref.shape[-1]
    dv = L // G
    R = G * C
    log_c = C.bit_length() - 1

    @pl.when(pl.program_id(2) == 0)
    def _():
        s_out_ref[...] = s_ref[...]

    S = s_out_ref[0, 0]                                      # [dk, L]
    q = jnp.concatenate([q_ref[0, i] for i in range(G)], axis=0)  # [R, dk]
    k = jnp.concatenate([k_ref[0, i] for i in range(G)], axis=0)
    kt = kt_ref[0, 0, 0]                                     # [dk, R]
    v = v_ref[0]                                             # [C, L]
    ri = lax.broadcasted_iota(jnp.int32, (R, R), 0)
    ci = lax.broadcasted_iota(jnp.int32, (R, R), 1)
    lane = lax.broadcasted_iota(jnp.int32, (1, L), 1)
    col = lax.broadcasted_iota(jnp.int32, (1, R), 1)
    eye = ri == ci
    same = (ri >> log_c) == (ci >> log_c)
    lower = same & (ri >= ci)

    # every log-decay is the sum of g over the tokens it spans, taken
    # directly (a difference of two running sums would cancel their
    # rounding against each other's): gc_i over a head's tokens up to i,
    # rest_i over those after i, D[i, j] = exp(sum over j < t <= i)
    g_row = gb_ref[0, 0, 0, 0:1]                             # [1, R]
    beta = jnp.sum(jnp.where(eye, gb_ref[0, 0, 0, 1:2], 0.0), axis=1,
                   keepdims=True)                            # [R, 1]
    gc = jnp.sum(jnp.where(lower, g_row, 0.0), axis=1, keepdims=True)
    rest = jnp.sum(jnp.where(same & (ci > ri), g_row, 0.0), axis=1,
                   keepdims=True)
    # the same along the lanes, copied exactly
    rest_row = jnp.sum(jnp.where(eye, rest, 0.0), axis=0, keepdims=True)
    total = jnp.sum(jnp.where(same, g_row, 0.0), axis=1, keepdims=True)
    spans = _dot(jnp.where(lower, g_row, 0.0),
                 jnp.where(same & (ri > ci), 1.0, 0.0))      # [R, R]
    decay = jnp.exp(jnp.where(lower, spans, -jnp.inf))

    kb = k * beta
    kk_qk = _dot(jnp.concatenate([kb, q], axis=0), kt)       # [2R, R]
    a = jnp.where(same & (ri > ci), kk_qk[:R] * decay, 0.0)
    qk = jnp.where(lower, kk_qk[R:] * decay, 0.0)
    t = _unit_lower_inverse(a, C, G)                         # [C, R]
    # (I + A)^-1 [K_b exp(gc) | V_b]: the chunk's rank-one updates, undone
    # of their dependence on each other
    w = _dot(_stack(t, C, col, G), kb * jnp.exp(gc))         # [R, dk]
    u = _dot(t, _stack(v, dv, lane, G) * beta)               # [C, L]
    ws_qs = _dot(jnp.concatenate([w, q * jnp.exp(gc)], axis=0), S)
    new = _stack(u - _unstack(ws_qs[:R], dv, lane, G), dv, lane, G)
    # [tril(Q K^T D) ; (K exp(rest))^T] V_new in one product
    out = _dot(jnp.concatenate([_unstack(qk, C, col, G),
                                kt * jnp.exp(rest_row)], axis=0),
               new)                                          # [C + dk, L]
    o_ref[0] = _unstack(ws_qs[R:], dv, lane, G) + out[:C]
    # each head's decay over the whole chunk, on its lanes of S
    whole = jnp.exp(total)                                   # [R, 1]
    lanes_decay = jnp.zeros((1, L), jnp.float32)
    for i in range(G):
        lanes_decay = jnp.where(lane >= i * dv, whole[i * C:i * C + 1],
                                lanes_decay)
    s_out_ref[0, 0] = S * lanes_decay + out[C:]


@functools.partial(jax.jit, static_argnames=("interpret",))
def _chunk_call(q, k, kt, v, gb, state, interpret):
    B, H, Tp, dk = q.shape
    _, P, _, L = state.shape
    G, N = H // P, gb.shape[2]
    C = Tp // N
    R = G * C
    return pl.pallas_call(
        _chunk_kernel,
        grid=(B, P, N),
        in_specs=[
            pl.BlockSpec((1, G, C, dk), lambda b, p, n: (b, p, n, 0)),
            pl.BlockSpec((1, G, C, dk), lambda b, p, n: (b, p, n, 0)),
            pl.BlockSpec((1, 1, 1, dk, R), lambda b, p, n: (b, p, n, 0, 0)),
            pl.BlockSpec((1, C, L), lambda b, p, n: (b, n, p)),
            pl.BlockSpec((1, 1, 1, 2, R), lambda b, p, n: (b, p, n, 0, 0)),
            pl.BlockSpec((1, 1, dk, L), lambda b, p, n: (b, p, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, C, L), lambda b, p, n: (b, n, p)),
            pl.BlockSpec((1, 1, dk, L), lambda b, p, n: (b, p, 0, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((B, Tp, P * L), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, jnp.float32)],
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        # a grid step's products (the doubling's 2 x log2(C) among them)
        cost_estimate=pl.CostEstimate(
            flops=2 * B * P * N * (
                2 * R * dk * R + 2 * (C.bit_length() - 1) * C * R * R
                + R * R * R + R * R * dk + C * R * L + 2 * R * dk * L
                + (C + dk) * R * L),
            transcendentals=B * P * N * R * (R + 3),
            bytes_accessed=4 * (3 * q.size + kt.size + 2 * v.size + gb.size
                                + 2 * state.size)),
        name="gated_delta_chunk",
        interpret=interpret,
    )(q, k, kt, v, gb, state)


def gated_delta_chunked_packed(q, k, v, g, beta, state, mask=None, *,
                               chunk: int = CHUNK,
                               interpret: bool | None = None):
    """:func:`~.gated_delta.gated_delta_chunked` on a PACKED state, through
    the fused kernel: ``q``, ``k`` ``[B, T, H, dk]``, ``v`` ``[B, T, H,
    dv]``, ``g``, ``beta`` ``[B, T, H]``, ``state`` ``[B, H/G, dk, G*dv]``
    float32 (:func:`state_layout`'s, or ``G`` 1), ``mask`` ``[B, T]``
    bool. Returns ``(o [B, T, H, dv] float32, state)``, the state in the
    operand's own memory where the caller donates it. In front, XLA lays
    ``q`` and ``k`` out head-major and ``k`` transposed a chunk, ``g``
    and ``beta`` as lane rows a chunk, each padded to whole chunks; ``v``
    and ``o`` keep the ``[B, T, H*dv]`` order, whose ``G dv`` lanes a
    packed row are whole tiles. Interpreted off a TPU unless ``interpret``
    says."""
    B, T, H, dk = q.shape
    dv = v.shape[-1]
    P, L = state.shape[1], state.shape[-1]
    if (state.dtype != jnp.float32 or H % P
            or state.shape != (B, P, dk, H // P * dv)):
        raise ValueError(
            f"state {state.dtype}{list(state.shape)} is no packing of "
            f"float32 {[B, H, dk, dv]}")
    if chunk & (chunk - 1) or chunk % _SUBLANES:
        raise ValueError(f"chunk {chunk} is no power of two of whole "
                         "sublane tiles")
    if interpret is None:
        interpret = jax.devices()[0].platform != "tpu"
    G = H // P
    g, beta = _masked(g, beta, mask)
    pad = -T % chunk
    N = (T + pad) // chunk

    def padded(a):           # [B, T, ...] -> [B, N * chunk, ...] float32
        a = a.astype(jnp.float32)
        return jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))

    def heads_major(a):      # [B, T, H, dk] -> [B, H, N * chunk, dk]
        return jnp.swapaxes(padded(a), 1, 2)

    def lanes(a):            # [B, T, H, ...] -> [B, P, N, ..., G * chunk]
        a = padded(a).reshape(B, N, chunk, P, G, -1)
        return jnp.transpose(a, (0, 3, 1, 5, 4, 2)).reshape(
            B, P, N, -1, G * chunk)

    o, state = _chunk_call(heads_major(q), heads_major(k), lanes(k),
                           padded(v.reshape(B, T, H * dv)),
                           lanes(jnp.stack([g, beta], axis=-1)), state,
                           interpret)
    return o[:, :T].reshape(B, T, H, dv), state


def _chunked_xla(q, k, v, g, beta, state, mask):
    """The XLA form on the state as it is carried: an :func:`unpack`, the
    chunked scan, a :func:`pack`."""
    o, state = gated_delta_chunked(q, k, v, g, beta,
                                   unpack(state, q.shape[2]), mask)
    return o, pack(state)


@jax.custom_vjp
def gated_delta_chunked_kernel(q, k, v, g, beta, state, mask):
    """:func:`gated_delta_chunked_packed` as a differentiable function.
    The kernel has no backward pass of its own: a gradient recomputes
    through the XLA form."""
    return gated_delta_chunked_packed(q, k, v, g, beta, state, mask)


def _chunk_fwd(q, k, v, g, beta, state, mask):
    return (gated_delta_chunked_kernel(q, k, v, g, beta, state, mask),
            (q, k, v, g, beta, state, mask))


def _chunk_bwd(res, ct):
    *operands, mask = res
    _, vjp = jax.vjp(lambda *a: _chunked_xla(*a, mask), *operands)
    return (*vjp(ct), None)


gated_delta_chunked_kernel.defvjp(_chunk_fwd, _chunk_bwd)


def gated_delta_chunked_carried(q, k, v, g, beta, state, mask=None, *,
                                form: str):
    """A run of tokens a row on the state AS IT IS CARRIED, by ``form``
    (:func:`state_chunk`'s answer): the kernel, or the XLA form through an
    :func:`unpack` and a :func:`pack`."""
    if form == "kernel":
        return gated_delta_chunked_kernel(q, k, v, g, beta, state, mask)
    return _chunked_xla(q, k, v, g, beta, state, mask)
