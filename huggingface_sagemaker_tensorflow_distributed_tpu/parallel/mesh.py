"""Device-mesh construction: the distribution substrate.

TPU-native replacement for the reference's L3 distributed runtime (the
Horovod-style rank topology at reference ``scripts/train.py:24-31`` and the
in-process ``tf.distribute.MirroredStrategy`` at
``scripts/singe_node_train.py:40``). Both of the reference's strategies —
multi-process DP and single-host mirrored DP — collapse here into ONE
code path: a ``jax.sharding.Mesh`` whose shape decides the parallelism.
A 1-chip mesh, an 8-chip host, and a multi-host v5e-32 slice all run the
same trainer; only the mesh shape differs (SURVEY.md §7 "ambient" model).

Axes:

- ``dcn``: OUTERMOST data parallelism across slices/pods connected by
  data-center network rather than ICI (multi-slice training). Only the
  once-per-step gradient all-reduce crosses it; every other collective
  (tensor, seq, expert, pipe) stays inside a slice. Groups devices by
  ``slice_index`` (TPU multi-slice) or ``process_index`` (CPU
  simulation), so the axis boundary IS the slow-network boundary.
- ``data``: pure data parallelism (the reference's only axis —
  ``hvd.size()`` at ``scripts/train.py:112``).
- ``fsdp``: data parallelism with parameter/optimizer sharding (ZeRO-3
  style; absent in the reference, SURVEY.md §2).
- ``expert``: expert parallelism for MoE layers (``models/moe.py``):
  the expert dimension of expert weights is sharded over it, and it
  doubles as a data axis for the non-expert parts of the model (the
  standard MoE layout — token all-to-alls ride this axis).
- ``pipe``: pipeline parallelism (``models/pipeline.py``): the stacked
  layer dimension of a pipelined encoder is sharded over it; microbatch
  handoffs between stages are collective-permutes along this axis.
- ``tensor``: Megatron-style tensor parallelism inside attention/FFN.
- ``seq``: sequence/context parallelism (ring attention) for long
  sequences.

Device order: ``jax.devices()`` orders TPU devices so that nearest
neighbours on the ICI torus are adjacent; we reshape row-major with
``data`` outermost and ``tensor``/``seq`` innermost, so the
bandwidth-hungry tensor/sequence collectives ride intra-host ICI links
while the once-per-step gradient reduction spans hosts (DCN when
crossing slices).
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass

import jax
import numpy as np
from jax.sharding import Mesh

AXIS_DCN = "dcn"
AXIS_DATA = "data"
AXIS_FSDP = "fsdp"
AXIS_EXPERT = "expert"
AXIS_PIPE = "pipe"
AXIS_TENSOR = "tensor"
AXIS_SEQ = "seq"

MESH_AXES = (AXIS_DCN, AXIS_DATA, AXIS_FSDP, AXIS_EXPERT, AXIS_PIPE,
             AXIS_SEQ, AXIS_TENSOR)


def data_axis_names() -> tuple[str, ...]:
    """Axes over which a global batch is sharded (and grads reduced).

    ``dcn`` leads: it is pure (cross-slice) data parallelism, so batches
    shard over it and the gradient reduction's outer ring rides DCN —
    the only traffic that leaves a slice. ``expert`` is a data axis for
    everything outside MoE layers: tokens are sharded over it like any
    other batch split, and the MoE dispatch einsum reshards them
    expert-major (an all-to-all XLA derives from the sharding
    annotations)."""
    return (AXIS_DCN, AXIS_DATA, AXIS_FSDP, AXIS_EXPERT)


@dataclass(frozen=True)
class MeshConfig:
    """Mesh shape request. ``dp=-1`` absorbs all remaining devices.
    ``dcn_dp > 1`` adds an outer data-parallel axis across slices
    (multi-slice: grads all-reduce hierarchically, outer ring over DCN)."""

    dp: int = -1
    fsdp: int = 1
    ep: int = 1
    pp: int = 1
    tp: int = 1
    sp: int = 1
    dcn_dp: int = 1

    def resolve(self, n_devices: int) -> tuple[int, ...]:
        fixed = (self.dcn_dp * self.fsdp * self.ep * self.pp * self.tp
                 * self.sp)
        if n_devices % fixed != 0:
            raise ValueError(
                f"dcn_dp*fsdp*ep*pp*tp*sp={fixed} does not divide device "
                f"count {n_devices}"
            )
        dp = self.dp if self.dp != -1 else n_devices // fixed
        if dp * fixed != n_devices:
            raise ValueError(
                f"mesh {self.dcn_dp}x{dp}x{self.fsdp}x{self.ep}x{self.pp}"
                f"x{self.sp}x{self.tp} != {n_devices} devices"
            )
        return (self.dcn_dp, dp, self.fsdp, self.ep, self.pp, self.sp,
                self.tp)


# Ambient mesh: modules deep inside a model (e.g. the ring-attention
# dispatch in ops/attention.py) need the mesh without threading it
# through every Flax call signature. The Trainer enters ``use_mesh``
# around every jitted-step call (tracing happens at first call), so the
# mesh a step traces with is always the trainer's own — the same ambient
# model as the reference's strategy scope
# (``scripts/singe_node_train.py:41``). Strictly LIFO: use the context
# manager, never mutate the stack directly.
_CURRENT_MESH: list[Mesh] = []


def current_mesh() -> Mesh:
    if not _CURRENT_MESH:
        raise RuntimeError(
            "no ambient mesh set — use parallel.mesh.use_mesh(mesh) "
            "around tracing (the Trainer does this for its steps)")
    return _CURRENT_MESH[-1]


def maybe_current_mesh() -> Mesh | None:
    return _CURRENT_MESH[-1] if _CURRENT_MESH else None


class use_mesh:
    """Push an ambient mesh for the duration of a block (LIFO)."""

    def __init__(self, mesh: Mesh):
        self.mesh = mesh

    def __enter__(self):
        _CURRENT_MESH.append(self.mesh)
        return self.mesh

    def __exit__(self, *exc):
        _CURRENT_MESH.pop()


def build_mesh(config: MeshConfig | None = None, devices=None) -> Mesh:
    """Build the global mesh over all addressable devices.

    Single-chip, single-host and multi-host all go through here; under
    multi-host each process sees the same global mesh
    (``jax.devices()`` is global after ``jax.distributed.initialize``) —
    the TPU-native equivalent of Horovod's rendezvous
    (reference ``scripts/train.py:24``).
    """
    config = config or MeshConfig()
    devices = devices if devices is not None else jax.devices()
    shape = config.resolve(len(devices))
    if config.dcn_dp > 1:
        devices = _dcn_grouped(list(devices), config.dcn_dp)
    arr = np.asarray(devices).reshape(shape)
    return Mesh(arr, MESH_AXES)


def _dcn_grouped(devices: list, dcn_dp: int) -> list:
    """Order devices so consecutive blocks of ``len/dcn_dp`` share a
    slice (TPU multi-slice ``slice_index``) or a process (CPU/host
    simulation) — the ``dcn`` axis boundary must be the slow-network
    boundary or the whole point of the hierarchy is lost. Falls back to
    the given order when no grouping attribute distinguishes devices
    (single-process virtual meshes: any split is equally 'local')."""
    def group_key(d):
        s = getattr(d, "slice_index", None)
        return s if s is not None else d.process_index
    groups: dict = {}
    for d in devices:
        groups.setdefault(group_key(d), []).append(d)
    if len(groups) > 1:
        if len(groups) % dcn_dp != 0:
            raise ValueError(
                f"dcn_dp={dcn_dp} does not divide the {len(groups)} "
                f"slices/processes — each dcn block must hold whole slices")
        sizes = {len(g) for g in groups.values()}
        if len(sizes) > 1:
            raise ValueError(f"uneven slice sizes {sizes} under dcn_dp")
        if len(groups) > dcn_dp:
            # blocks then span multiple slices: the inner (ICI-assumed)
            # axes cross DCN every collective — legal, but almost never
            # what you want; dcn_dp should equal the slice count
            import logging
            logging.getLogger(__name__).warning(
                "dcn_dp=%d < %d slices/processes: each dcn block spans "
                "%d slices, so inner-axis collectives cross DCN; set "
                "dcn_dp=%d to align the hierarchy with the network",
                dcn_dp, len(groups), len(groups) // dcn_dp, len(groups))
        devices = [d for k in sorted(groups) for d in groups[k]]
    return devices


@functools.lru_cache(maxsize=None)
def tensor_parallel_mesh(tp: int) -> Mesh:
    """A pure tensor-parallel serving mesh: ``dp=1 × tp`` over the
    FIRST ``tp`` addressable devices. Cached so every caller asking for
    the same degree gets the SAME ``Mesh`` object — mesh identity feeds
    hashed jit static keys (the serve engine's :class:`CachePlan`
    carries ``NamedSharding``s built from it), and a fresh-but-equal
    mesh per engine build would silently retrace every step the warmup
    already compiled."""
    if tp < 1:
        raise ValueError(f"tensor-parallel degree must be >= 1, got {tp}")
    devices = jax.devices()
    if len(devices) < tp:
        raise ValueError(
            f"tensor-parallel degree {tp} needs {tp} devices, "
            f"{len(devices)} addressable")
    return build_mesh(MeshConfig(dp=1, tp=tp), devices=devices[:tp])


def world_size(mesh: Mesh) -> int:
    """Total device count — ``hvd.size()`` parity (reference train.py:112)."""
    return math.prod(mesh.devices.shape)


def data_parallel_size(mesh: Mesh) -> int:
    """Number of data-parallel replicas (dcn × data × fsdp × expert)."""
    return (mesh.shape.get(AXIS_DCN, 1) * mesh.shape[AXIS_DATA]
            * mesh.shape[AXIS_FSDP] * mesh.shape.get(AXIS_EXPERT, 1))
