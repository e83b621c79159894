"""Cross-engine KV block-set transport (ISSUE 18).

ONE primitive — :func:`migrate_request` — moves a live request between
two :class:`~.engine.ServeEngine` instances with zero re-prefill: the
request's block set leaves the source pools through
:func:`~.paged_kv.extract_blocks` (full LOGICAL blocks on host — value
pools, int8 scale planes, and draft pools ride together, and a
tensor-parallel source's shards are already assembled by the
``device_get``), the scheduler-side :class:`~.scheduler.Request`
transplants with its generated tail, sampled seed, SLO riders and
timeline stamps intact, and the destination re-admits it through the
swapped-request path (:meth:`~.scheduler.Scheduler._reserve_swapped`):
allocate exactly the set's blocks from the DESTINATION pool, scatter
before any dispatch reads the table, resume in DECODE. Because the
host payload is engine-geometry-free, inserting into a destination
with a different tensor-parallel degree re-shards the KV heads axis
as a side effect of the destination's own committed pool shardings —
no new pool math, which is the point of the BlockSet layout.

Token exactness falls out of two existing invariants: the generated
tokens never leave ``req.output`` (the decode feed is ``output[-1]``
on whichever engine runs it), and token ``n``'s sampling key is
``fold_in(PRNGKey(seed), n)`` — a pure function of (seed, n), so a
moved sampled stream is bitwise the unmoved one.

The Router cashes this in three ways (ISSUE 18): disaggregated
prefill/decode fleets (``Router(roles=...)``), live migration of
RESIDENT requests off a draining replica, and length-aware placement
over heterogeneous (mixed-TP) fleets.
"""

from __future__ import annotations

import time
from typing import Optional

from huggingface_sagemaker_tensorflow_distributed_tpu import obs
from huggingface_sagemaker_tensorflow_distributed_tpu.serve.paged_kv import (
    extract_blocks,
)
from huggingface_sagemaker_tensorflow_distributed_tpu.serve.scheduler import (
    DECODE,
    WAITING,
)

__all__ = ["TransportError", "migrate_request", "can_accept",
           "pool_signature"]


class TransportError(RuntimeError):
    """A request cannot move: not resident on the source, incompatible
    pool geometry, or a destination too small to ever hold it."""


def pool_signature(engine) -> tuple:
    """The engine's LOGICAL pool geometry: ``(block_size, per-pool
    (block shape, dtype), draft ditto)``. Shapes are global (a sharded
    pool reports its unsharded shape), so two engines at different
    tensor-parallel degrees over the same model compare EQUAL — the
    transportability contract: equal signatures mean a :class:`~.
    paged_kv.BlockSet` extracted from one scatters bitwise into the
    other."""
    def sig(pools):
        return tuple((tuple(int(d) for d in p.shape[1:]), str(p.dtype))
                     for p in pools)
    draft = sig(engine._d_pools) if engine.speculative else None
    return (int(engine.blocks.block_size), sig(engine._pools), draft)


def can_accept(dst, req, live: bool = False) -> bool:
    """True when ``dst`` could EVER hold ``req``: the submit-time
    worst-case block need (padded prompt, full generation + decode
    lookahead, preemption-folded re-prefill) against the destination's
    own chunk grid, model length, and whole pool — the same formula
    :meth:`~.scheduler.Scheduler.submit` validates, re-run because a
    heterogeneous destination's geometry may be smaller than the
    engine the request was originally admitted to.

    With ``live=True`` (ISSUE 20, admission-aware placement) the
    probe additionally requires the worst case to fit the pool's
    CURRENT headroom (:meth:`~.paged_kv.BlockManager.can_allocate` —
    free + evictable cached blocks), so a router can skip a
    destination that is full RIGHT NOW for a peer with room. Purely a
    read: no refcount, LRU, or allocation state moves either way."""
    s = dst.sched
    total = len(req.prompt) + req.max_new_tokens
    if total + s.decode_lookahead - 1 > s.max_model_len:
        return False
    worst = max(s.padded_prompt_len(req),
                total + s.decode_lookahead - 1,
                -(-(total - 1) // s.prefill_chunk) * s.prefill_chunk)
    need = s.blocks.blocks_for(worst)
    if need > s.blocks.num_blocks - 1:
        return False
    return s.blocks.can_allocate(need) if live else True


def migrate_request(src, dst, rid: int, prefetched=None,
                    extract_s: float = 0.0) -> Optional[dict]:
    """Move resident request ``rid`` from ``src`` to ``dst``.

    A DECODE resident moves HOT: its context's block set is extracted
    to host, the source's blocks are released, and the request enters
    the destination's queue at the FRONT (it already held residency —
    a migration must not re-queue it behind unadmitted work) carrying
    the set as its ``swap_set``; the destination's next admission
    allocates from its own pool, scatters, and resumes decode on the
    committed tail. A mid-PREFILL resident (nothing generated yet)
    moves COLD — no payload, the destination re-runs its prefill —
    which keeps drains latency-bounded without shipping half-written
    block spans.

    The source's in-flight pipeline is landed first (the preemption
    rule: migration acts on COMMITTED state only); committing may
    finish the request, in which case there is nothing to move and
    ``None`` is returned. Otherwise returns ``{"rid", "bytes",
    "context_len", "cold"}``. Raises :class:`TransportError` when the
    request is not resident on ``src``, the engines' pool geometries
    differ, or ``dst`` could never hold the request.

    ``prefetched`` (ISSUE 20, the PR 18 drain follow-up) is a
    :class:`~.paged_kv.BlockSet` the caller already extracted for
    this request as part of a batched cohort pull
    (:func:`~.paged_kv.extract_block_sets` — one ``device_get`` per
    victim cohort instead of one per request), with ``extract_s`` its
    amortized share of the cohort's extraction seconds. It is used
    only when it still matches the slot's committed context (the
    caller must have landed the source pipeline before prefetching);
    otherwise the per-request extraction runs as before — semantics,
    migration count, and tokens are identical either way.
    """
    if src is dst:
        raise TransportError(
            f"request {rid}: source and destination are the same engine")
    if src._stateful or dst._stateful:
        raise TransportError(
            f"request {rid}: migrate_request is not wired for a model "
            "with recurrent state: a BlockSet carries blocks and no "
            "state (ROADMAP R3: state snapshots)")
    if pool_signature(src) != pool_signature(dst):
        raise TransportError(
            f"request {rid}: engine pool geometries differ "
            f"({pool_signature(src)} vs {pool_signature(dst)})")
    if rid in src.finished:
        return None
    slot = next((s for s in src.sched.slots
                 if s.request is not None and s.request.rid == rid), None)
    if slot is None:
        raise TransportError(
            f"request {rid} is not resident on the source engine")
    req = slot.request
    if not can_accept(dst, req):
        raise TransportError(
            f"request {rid} can never fit the destination engine "
            f"(max_model_len {dst.sched.max_model_len}, pool "
            f"{dst.blocks.num_blocks - 1} blocks)")
    # land any in-flight dispatch before touching the slot (the same
    # committed-state rule preemption follows) — the commit may FINISH
    # the request, which makes the migration a no-op
    with src._mesh_ctx():
        if src._pending is not None:
            src._flush("migrate")
        if src._pending_spec is not None:
            pending, src._pending_spec = src._pending_spec, None
            src._commit_spec(pending)
    if rid in src.finished:
        return None
    # the destination's re-admission closes this as the request's
    # migration-hold interval (the timeline's "preempted" phase — a
    # migrated request is off-accelerator either way). The same stamp
    # opens the transport-hop clock the destination's restore apply
    # closes (ISSUE 19).
    req.preempt_t = time.perf_counter()
    req.migrate_out_t = req.preempt_t
    cold = req.state != DECODE
    if cold:
        nbytes, ctx = 0, 0
        req.migrate_extract_s = 0.0
    else:
        n = src.blocks.blocks_for(slot.context_len)
        if prefetched is not None and prefetched.n_blocks == n:
            req.swap_set = prefetched
            req.migrate_extract_s = float(extract_s)
        else:
            t0 = time.perf_counter()
            with src._mesh_ctx():
                req.swap_set = extract_blocks(
                    src._pools, slot.table[:n],
                    d_pools=src._d_pools if src.speculative else None)
            req.migrate_extract_s = time.perf_counter() - t0
        req.swap_context = slot.context_len
        nbytes, ctx = req.swap_set.nbytes, slot.context_len
    src.blocks.release(slot.table)
    slot.clear()
    src._keys.pop(rid, None)
    req.state = WAITING
    req.hop += 1
    src.migrations_out += 1
    dst.adopt_resident(req, from_replica=src.replica)
    if cold:
        # a cold move lands no destination-side restore, so the
        # migrate event is emitted here; a HOT move's event comes from
        # the destination's restore apply, which knows restore_s
        kw = {}
        if src.replica is not None:
            kw["from_replica"] = src.replica
        if dst.replica is not None:
            kw["to_replica"] = dst.replica
        if req.trace_id:
            kw["trace_id"] = req.trace_id
            kw["hop"] = req.hop
        obs.serve("migrate", request=rid, migration_bytes=0,
                  restore_s=0.0, **kw)
    return {"rid": rid, "bytes": nbytes, "context_len": ctx,
            "cold": cold}
