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
    _masked,
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
