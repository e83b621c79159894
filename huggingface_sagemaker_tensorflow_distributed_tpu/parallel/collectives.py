"""Collective helpers used inside jitted/shard_mapped code.

TPU-native replacement for the reference's L-1 communication layer.
The reference's entire collective vocabulary (SURVEY.md §5.8) is:
rendezvous (``scripts/train.py:24``), rank-0 broadcast
(``scripts/train.py:133``), and per-step gradient allreduce
(``scripts/train.py:114``) — all implemented in Horovod/NCCL C++.
Here the same operations are XLA collectives over ICI/DCN: under ``jit``
with sharded inputs XLA inserts them automatically from sharding
annotations; under ``shard_map`` (used by the ring-attention path) they
are written explicitly with ``lax`` primitives. No hand-written
transport exists because the TPU runtime provides it below XLA.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax


def ppermute_shift(x, axis_name: str, shift: int = 1):
    """Ring shift along a mesh axis — the KV-rotation step of ring
    attention (``parallel/ring_attention.py``). ``shift=1`` sends to the
    next device on the ring; ``shift=-1`` to the previous."""
    n = lax.axis_size(axis_name)
    perm = [(i, (i + shift) % n) for i in range(n)]
    return lax.ppermute(x, axis_name, perm)


def param_fingerprint(params) -> jnp.ndarray:
    """Scalar checksum of a param tree (sum of squares in fp32) — the
    per-replica quantity ``replica_divergence`` compares across devices."""
    leaves = jax.tree.leaves(params)
    acc = jnp.zeros((), jnp.float32)
    for leaf in leaves:
        acc = acc + jnp.sum(jnp.asarray(leaf, jnp.float32) ** 2)
    return acc


class ReplicaDivergenceError(RuntimeError):
    """Raised when replicas of the parameters disagree across devices."""


def make_replica_divergence_fn(mesh, shardings):
    """Build the jitted replica-divergence pass once per (mesh, sharding
    tree) — callers on a hot path (the Trainer's checkpoint boundaries)
    must cache the returned function, or every call pays a retrace +
    XLA compile of the shard_map over the whole param tree.

    Every device computes ``param_fingerprint`` of its PHYSICAL local
    shards under ``shard_map`` (so real per-device buffers are read, not
    the SPMD fiction that replicas are equal), producing one checksum per
    device. Parameters are replicated along the ``data`` and ``seq`` mesh
    axes by the sharding rules, so the checksum grid must be constant
    along those axes; the return value is the max relative deviation —
    0.0 when all replicas agree bit-for-bit.

    This is the structural form of the replica-consistency guarantee the
    reference gets from Horovod's rank-0 broadcast + allreduce
    (``scripts/train.py:114,133``) and otherwise leaves to convention
    (the worker-0 checkpoint comment, ``scripts/train.py:135-137``):
    silent divergence (flaky interconnect, memory corruption, a host
    feeding different data) is detected instead of assumed away. Cost
    per call of the returned fn: one elementwise pass over the local
    params + one tiny cross-device comparison; only a scalar leaves the
    device."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from huggingface_sagemaker_tensorflow_distributed_tpu.parallel.mesh import (
        AXIS_DATA,
        AXIS_DCN,
        AXIS_EXPERT,
        AXIS_SEQ,
    )

    axes = tuple(mesh.axis_names)
    in_specs = jax.tree.map(lambda s: s.spec, shardings,
                            is_leaf=lambda x: isinstance(x, NamedSharding))

    def _mentions_expert(spec) -> bool:
        for entry in spec:
            entry = entry if isinstance(entry, tuple) else (entry,)
            if AXIS_EXPERT in entry:
                return True
        return False

    # Expert-sharded leaves (MoE weights) legitimately differ along the
    # ``expert`` axis, so they get their own checksum grid checked over
    # data/seq only; everything else is replicated along expert too and
    # is checked along all three.
    def local_checksum(p):
        plain, expert = [], []
        for leaf, spec in zip(jax.tree.leaves(p),
                              jax.tree.leaves(in_specs,
                                              is_leaf=lambda s: isinstance(s, P))):
            (expert if _mentions_expert(spec) else plain).append(leaf)
        shape = (1,) * len(axes)
        return (param_fingerprint(plain).reshape(shape),
                param_fingerprint(expert).reshape(shape))

    # graftlint: allow[R3] no static key: the only argument is the traced param pytree; mesh/specs are closed over at build time (one compile per divergence-checker instance)
    @jax.jit
    def compute(p):
        plain_grid, expert_grid = jax.shard_map(
            local_checksum, mesh=mesh,
            in_specs=(in_specs,), out_specs=(P(*axes), P(*axes)))(p)
        dev = jnp.zeros((), jnp.float32)
        for grid, check_axes in ((plain_grid, (AXIS_DCN, AXIS_DATA,
                                               AXIS_SEQ, AXIS_EXPERT)),
                                 (expert_grid, (AXIS_DCN, AXIS_DATA,
                                                AXIS_SEQ))):
            for ax in check_axes:
                if ax in axes and mesh.shape[ax] > 1:
                    i = axes.index(ax)
                    mean = jnp.mean(grid, axis=i, keepdims=True)
                    dev = jnp.maximum(dev, jnp.max(jnp.abs(grid - mean)))
        scale = jnp.maximum(
            jnp.maximum(jnp.max(jnp.abs(plain_grid)), jnp.max(jnp.abs(expert_grid))),
            1e-30)
        return dev / scale

    return compute


def replica_divergence(params, mesh, shardings) -> jnp.ndarray:
    """One-shot convenience over ``make_replica_divergence_fn`` (compiles
    each call — fine for tests/tools, not for the step loop)."""
    return make_replica_divergence_fn(mesh, shardings)(params)
