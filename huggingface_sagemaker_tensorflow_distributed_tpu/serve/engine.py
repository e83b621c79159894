"""The serving engine: continuous batching over a paged KV cache with
prefill/decode disaggregation, a width-bucketed decode fast path, and
optional speculative decoding.

Architecture (ISSUE 3 tentpole + ISSUE 5 fast path + ISSUE 6
speculation; vLLM + Orca + Sarathi + Leviathan lineage):

- **Paged KV** — one preallocated pool per KV leaf of the model's flax
  ``"cache"`` collection, ``[num_blocks, block_size, heads, head_dim]``.
  Persistent memory scales with blocks actually held (= tokens
  resident), not ``slots × max_model_len``. The jitted steps rebuild
  the model's cache pytree from the pools via
  ``ops.attention.gather_paged_kv`` (block-table gather), run the
  UNMODIFIED model decode path (same ``write_kv_cache`` protocol
  ``models/generate.py`` drives), then scatter the newly-written K/V
  back into the pools. No model code changes: paging is an addressing
  layer around the existing cache contract.
- **Width-bucketed gather** — the decode step AND the prefill
  dispatch are compiled at one small ladder of context-width buckets
  (``HSTD_SERVE_GATHER_BUCKETS`` / ``gather_buckets``; default
  quarter-width + full width). A decode iteration runs the smallest
  bucket covering the scheduler's per-iteration max resident context
  (``Scheduler.max_decode_context``); a prefill dispatch runs the
  smallest bucket covering ``max(start) + chunk`` over its rows
  (ISSUE 26). When most contexts are short the step's KV read traffic
  (and the attention mask/logits width behind it) shrinks from
  ``max_model_len`` to the bucket — the read-waste elimination of
  PagedAttention's motivating analysis. Decode growth is immediate
  (correctness), shrinking has hysteresis so bucket churn is bounded;
  every decode switch is telemetered (``bucket_switch`` serve event +
  ``serve/gather_bucket`` series). Prefill keeps no state: each
  dispatch picks its own bucket, and ``prefill_keys_needed`` /
  ``prefill_keys_attended`` say how well the ladder fits the traffic.
  Each (program, bucket) pair compiles exactly once, in ``warmup()``.
- **Iteration-level scheduling** — a fixed set of ``num_slots`` decode
  slots (static shapes, so after one warmup compile of each step
  function NOTHING retraces); requests admit/evict between decode
  steps (``serve/scheduler.py``).
- **Batched chunked prefill** — prompt ingestion packs up to
  ``prefill_batch`` prefilling slots' chunks into ONE fixed-shape
  dispatch (one row per slot; each row attends only the KV its own
  block table gathers, so cross-request isolation is structural — the
  property token-packing buys with ``make_segment_mask``, bought here
  by the paged addressing itself, and test-gated either way). The
  scheduler's adaptive budget is denominated in tokens-per-dispatch
  (Sarathi-style): a full decode batch admits one chunk's tokens per
  iteration (bounding the decode stall a long prompt can inject), and
  every idle decode slot buys one more chunk, packed into as few
  dispatches as possible — which is what cuts TTFT under bursty
  arrivals.
- **Copy-on-write prefix caching** (``prefix_cache``) — full
  block-aligned prompt-prefix chunks are indexed by a rolling hash
  chain (:class:`~.paged_kv.BlockManager`), so requests sharing a
  templated system prompt map their prefix onto SHARED refcounted KV
  blocks: prefill for the cached span is skipped entirely (a
  block-table write), admission charges only private blocks, and
  zero-ref cached blocks persist in an LRU until pool pressure evicts
  them. Writes into still-shared blocks privatize first via a
  device-side block copy (COW) — output stays token-exact vs cold
  start.
- **Fused paged-attention kernels** (``decode_path='paged_kernel'``) —
  the decode step's gather→dense-attend HBM round trip collapses into
  ONE fused read: the model's paged decode branch scatters each slot's
  new K/V (a latent-attention model: its one latent row) straight into
  the pools and attends via a Pallas kernel that walks the block tables
  inside the attention read (``ops/pallas_paged_attention.py`` for K/V
  pools; ``ops/pallas_paged_latent_attention.py`` for latent pools, the
  absorbed form, ISSUE 34) — no ``[S, H, width, D]`` intermediate, and
  no page past a slot's context is read. Rides the same bucket ladder
  (one compile per bucket), and returns what the gather step returns
  (the routed experts' pair counts among it). It IS the decode path
  where :func:`resolve_decode_path` finds it measured to win: on a TPU,
  without a mesh, for pools stored in a floating type that are all K/V
  of 128-wide heads or all latent rows of whole lane tiles. Everywhere
  else the gather path decodes: on a CPU (a kernel is interpret mode
  there: correct but slow, and the tests' reference is the gather),
  under a tensor-parallel mesh, for int8 pools, and in the speculative
  step. ``kernel='xla' | 'pallas'`` forces either.
- **int8 KV pools** (``kv_cache_dtype='int8'``) — pools store K/V as
  symmetric per-(position, head) int8 with fp32 scales riding parallel
  scale pools (written by the model's own ``kv_quantize`` protocol at
  scatter time, dequantized on read — in-tile under the kernel), which
  halves KV bytes per decode step end to end. Output is token-exact vs
  ``generate_causal`` on the SAME int8-cache config (quantization is
  deterministic, so recompute preemption and prefix sharing reproduce
  bitwise-identical pools); ``kv_pool_bytes`` sizes the pool by a
  memory budget, so int8 admits ~2x the requests of fp on equal bytes.
- **Speculative decoding** (``speculate_k``/``draft``) — per iteration
  a draft model (its own paged pools over the SAME block tables)
  proposes ``k`` tokens per running slot, then ONE width-(k+1) target
  verify — structurally just a wider bucketed decode, so it composes
  with the gather ladder — scores every window; the accepted prefix +
  bonus token commit, and rejected tokens roll back by an O(1)
  ``context_lens`` rewind (stale K/V hides behind the context-derived
  masks). Acceptance-rate × (k+1) decode tokens land per step with the
  output distribution unchanged (greedy: token-exact; sampled:
  Leviathan rejection acceptance).

- **Dispatch-ahead loop** (``overlap``, ISSUE 12) — the decode loop
  pipelines one iteration deep: dispatch N feeds N−1's un-fetched
  DEVICE tokens, ``device_get`` is deferred exactly one iteration,
  and the whole host side of the loop (commit, stamps, admission,
  bucket pick, block math, prefill staging) runs concurrently with
  the in-flight device step — the Orca/vLLM-style answer to host
  latency on the critical path. Token-value-dependent decisions are
  re-derived one step late (budget finishes from counts, EOS by
  discarding the wasted in-flight token) or drain the pipeline
  (preemption/KV pressure; ``overlap_flushes``); emitted tokens are
  identical to the serial loop's, which ``overlap='off'`` restores
  byte-for-byte. A LONE stream (decode occupancy 1, empty queue)
  auto-flushes to the serial schedule — there is no concurrent host
  work to hide, so the deferred fetch would only delay every token's
  delivery by one iteration (ISSUE 13 follow-up).
- **Tensor parallelism** (``mesh`` / ``HSTD_SERVE_TP``, ISSUE 13) —
  one engine serves a model bigger than a chip: params place
  Megatron-style over a ``tensor``-axis mesh
  (``parallel/sharding.py::param_shardings``) and every KV pool
  shards its HEADS axis (``kv_pool_sharding``; ``num_kv_heads % tp``
  rejected loudly, GQA included), so each device holds ``1/tp`` of
  every pool while block tables/context lens/token feeds stay
  replicated — the scheduler, BlockManager, prefix cache and overlap
  pipeline are untouched and output is token-identical to the
  single-device engine. The KV byte budget re-denominates PER DEVICE
  (``BlockManager.token_bytes`` = shard bytes/token), so the same
  per-chip ``kv_pool_bytes`` admits ~tp× the concurrent requests —
  the measurable capacity win even on CPU meshes.

- **Recurrent state beside the pools** (ISSUE 33) — a model with
  linear-attention layers (``models/olmo_hybrid.py``) carries, besides
  the paged K/V of its full-attention layers, state that is indexed by
  SLOT and never grows: :class:`CachePlan`'s ``state`` kind, one state
  pool a leaf (``[num_slots, ...]``, sized from ``num_slots`` alone).
  Every prefill dispatch and decode step hands each row its slot's rows
  and writes them back, the pools chaining
  through dispatch-ahead as the block pools do; a chunk that starts at 0
  starts from zeros in the program, and pad tails, pad rows and inactive
  slots hold the state still (the model's ``token_mask``). What carries
  or rolls back blocks only stands down or raises by name for such a
  model: the prefix index matches nothing (``stats().prefix_cache`` says
  ``off (recurrent state)``), ``swap``, ``speculate_k``, a mesh and
  ``transport.migrate_request`` raise; preemption recomputes from 0.
  ``state_form`` / ``state_rows`` on the step spans (with ``state_step``
  on a decode step's, ``state_chunk`` on a prefill dispatch's: the
  model's fused kernel or the XLA form), ``state_slots`` /
  ``kv_tokens_resident`` / ``prefill_tokens`` / ``state_slots_peak`` on
  the ledger lines, ``state_bytes_per_slot`` / ``state_pool_bytes`` in
  ``stats()`` and the report; a model without such layers has none of
  them and traces what it always did.

Decoding is greedy by default and token-for-token identical to
per-request ``generate_causal`` — the exactness gate
``tests/test_serve.py`` pins, including with bucketing enabled and
under preemption. Per-request ``temperature``/``top_k``/``top_p``
sampling rides the same dispatches via per-slot PRNG keys (the
filtering semantics of ``models/generate.py``'s ``_filter_top_p`` et
al., vectorized per row): the n-th token's key is
``fold_in(PRNGKey(seed), n)``, a pure function of (request seed, token
index), so sampled streams are bitwise-reproducible under a fixed seed
even across recompute preemption — the seeded-determinism gate.

Telemetry: ``serve`` events (``obs/schema.py``) for request lifecycle
(submit/admit/first_token/finish/preempt, submit carrying ``sampled``)
plus ``bucket_switch`` events, spans around every prefill and decode
dispatch, and pool-utilization/read-waste metrics. With ``timeline``
on (``HSTD_SERVE_TIMELINE``, default on — ISSUE 10) the engine
additionally stamps each request's phase transitions host-side and
emits a compact ``request_timeline`` event at finish/preempt-requeue
(queue / prefill / decode / preempted / overhead decomposition that
sums to e2e, plus a coalesced per-dispatch segment list: per-chunk
prefill incl. cached-prefix skip, per-iteration decode runs keyed by
gather bucket, speculative window acceptance, COW copies, admission
-block attribution) and a per-iteration ``iteration_ledger`` event
(phase mix, bucket, slots, tokens, pool pressure) — the inputs
``obsctl timeline|slo|tail`` reconstruct. All stamps are host-side
``perf_counter`` reads: the accounting mints zero compiled variants,
and ``timeline='off'`` is byte-identical to the pre-tracing stream.

Every iteration also accounts for its own wall time, sink or no sink
(ISSUE 25): ``_lap`` stamps the clock where the host changes what it is
doing and adds the stretch since the last stamp to one of four disjoint
parts — ``stage_s`` (host work before a dispatch or a fetch),
``dispatch_s`` (inside the jitted calls), ``fetch_wait_s`` (blocked on
the device), ``commit_s`` (host work on what a fetch or a dispatch
returned) — so ``dur_s >= stage_s + dispatch_s + fetch_wait_s +
commit_s`` on every ledger line, what is left being the gauges.
``gap_s`` is the caller's time since the previous iteration returned
(the ledger's own write falls there). :meth:`ServeEngine.
host_loop_totals` has the run's sums; the ``serve/*`` spans are the
same stretches by name, on the profiler's clock too (``obs/core.py``).
"""

from __future__ import annotations

import contextlib
import functools
import os
import time
from typing import Any, NamedTuple, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from huggingface_sagemaker_tensorflow_distributed_tpu import obs
from huggingface_sagemaker_tensorflow_distributed_tpu.models.generate import (
    _speculative_accept,
    sample_per_slot,
    self_draft,
    speculative_accept_greedy,
    warp_logits_per_slot,
)
from huggingface_sagemaker_tensorflow_distributed_tpu.ops.attention import (
    gather_paged_kv,
    scatter_paged_blocks,
    scatter_paged_kv,
)
from huggingface_sagemaker_tensorflow_distributed_tpu.serve.paged_kv import (
    BlockManager,
    extract_blocks,
    insert_blocks,
)
from huggingface_sagemaker_tensorflow_distributed_tpu.serve.scheduler import (
    DECODE,
    Request,
    Scheduler,
)

ENV_GATHER_BUCKETS = "HSTD_SERVE_GATHER_BUCKETS"
ENV_SPECULATE_K = "HSTD_SERVE_SPECULATE_K"
ENV_DRAFT_LAYERS = "HSTD_SERVE_DRAFT_LAYERS"
ENV_PREFIX_CACHE = "HSTD_SERVE_PREFIX_CACHE"
ENV_KERNEL = "HSTD_SERVE_KERNEL"
ENV_KV_DTYPE = "HSTD_SERVE_KV_DTYPE"
ENV_TIMELINE = "HSTD_SERVE_TIMELINE"
ENV_OVERLAP = "HSTD_SERVE_OVERLAP"
ENV_TP = "HSTD_SERVE_TP"
ENV_SWAP = "HSTD_SERVE_SWAP"
ENV_SWAP_BYTES = "HSTD_SERVE_SWAP_BYTES"


def parse_tp(spec) -> int:
    """The tensor-parallel degree knob (ISSUE 13): a positive int, the
    number of devices one engine shards its params + KV pools over.
    None reads ``HSTD_SERVE_TP`` (default 1 = the single-device
    engine). Rejects non-integers and non-positive values here; the
    divisibility contracts (device count, kv heads) are enforced where
    the mesh and pool shardings are built — with the offending figure
    named."""
    if spec is None:
        spec = os.environ.get(ENV_TP, "1") or "1"
    try:
        tp = int(str(spec).strip() or "1")
    except ValueError:
        raise ValueError(f"unparseable {ENV_TP} value {spec!r}: "
                         "expected a positive integer")
    if tp < 1:
        raise ValueError(f"{ENV_TP} must be >= 1, got {tp}")
    return tp


def parse_kernel(spec: Union[str, None]) -> Optional[str]:
    """The decode-kernel knob: ``xla`` forces the gather path (gather +
    dense attention — the reference, CPU-native), ``pallas`` the fused
    paged-decode kernel of the model's pools
    (``ops/pallas_paged_attention.py``, or for latent pools
    ``ops/pallas_paged_latent_attention.py`` — interpret-mode off TPU).
    None reads ``HSTD_SERVE_KERNEL``; with that unset too the answer is
    None: the engine chooses (:func:`resolve_decode_path`)."""
    if spec is None:
        spec = os.environ.get(ENV_KERNEL)
    if spec is None or not str(spec).strip():
        return None
    s = str(spec).strip().lower()
    if s not in ("xla", "pallas"):
        raise ValueError(f"unparseable {ENV_KERNEL} value {spec!r}: "
                         "expected xla | pallas")
    return s


# head sizes at which the fused kernel was measured to beat the gather
# path on the chip (tools/paged_decode_microbench.py; PERF.md §6, PR 29:
# at 1, 2, 4, 8 and 32 KV heads of 128, bf16 and float32; the kernel
# bounds its compute block in bytes, so the number of KV heads is no
# input here). 64 is not among them: such a pool is laid out block-minor
# on the v5e, no page of it is contiguous, and the kernel's call copies
# it whole
_KERNEL_HEAD_DIMS = (128,)


def resolve_decode_path(kernel: Optional[str], *, platform: str,
                        pool_kinds: Sequence[str], mesh: bool,
                        head_dim: int, kv_dtype: str) -> str:
    """Which way a decode step attends: ``paged_kernel`` (a fused kernel
    walks the block tables; ``_paged_decode_step``) or ``gather`` (a
    bucket-wide copy of the cache; ``_decode_step``). A pure function of
    what the engine can see: the backend's ``platform``, the kinds of the
    plan's pooled leaves (``kv`` | ``latent``), whether a tensor-parallel
    ``mesh`` is on, the pools' ``head_dim`` (a latent pool's row width)
    and storage ``kv_dtype`` (``fp`` | ``int8``), and the explicit
    ``kernel`` (``xla`` | ``pallas`` | None = choose). Whether the model
    routes tokens to experts is no input: both steps count its pairs.

    An explicit value wins, and ``pallas`` raises where no kernel has a
    form: a mesh, a plan that mixes K/V and latent pools (each kernel
    walks its own kind; no model holds both). Left to choose, a kernel is
    taken only where it was measured to win: on a TPU, no mesh, pools in
    a floating type (an int8 pool of two KV heads is stored head-major
    within a page on the v5e and would be copied whole for every call:
    rehearsal compile, PR 29), and then for K/V pools with a head size of
    ``_KERNEL_HEAD_DIMS`` (``ops/pallas_paged_attention.py``) and for
    latent pools whose rows are whole lane tiles
    (``ops/pallas_paged_latent_attention.py``: PERF.md 6, PR 34)."""
    kinds = set(pool_kinds)
    if kernel == "pallas":
        if mesh:
            raise ValueError(
                "kernel='pallas' does not compose with a tensor-parallel "
                "mesh: the fused paged kernel reads whole pools and "
                "would need a shard_map port — serve TP with the xla "
                "gather path (the kernel is a per-chip bandwidth "
                "optimization; TP is a capacity one)")
        if len(kinds) > 1:
            raise ValueError(
                "kernel='pallas': no fused paged kernel has a form for a "
                f"plan that mixes pool kinds ({sorted(kinds)}): serve "
                "this model with the xla gather path")
        return "paged_kernel"
    if kernel == "xla":
        return "gather"
    fits = ((kinds == {"kv"} and head_dim in _KERNEL_HEAD_DIMS)
            or (kinds == {"latent"} and head_dim % 128 == 0))
    chosen = platform == "tpu" and fits and not mesh and kv_dtype == "fp"
    return "paged_kernel" if chosen else "gather"


def parse_kv_dtype(spec: Union[str, None], model_default: str) -> str:
    """The pool-storage knob: ``fp`` or ``int8`` (int8 halves KV bytes
    per decode step; scales ride parallel fp32 pools). None reads
    ``HSTD_SERVE_KV_DTYPE``, falling back to the model config's own
    ``kv_cache_dtype``."""
    if spec is None:
        spec = os.environ.get(ENV_KV_DTYPE) or None
    if spec is None:
        return model_default
    s = str(spec).strip().lower()
    if s not in ("fp", "int8"):
        raise ValueError(f"unparseable {ENV_KV_DTYPE} value {spec!r}: "
                         "expected fp | int8")
    return s


def _parse_on_off(spec: Union[str, bool, None], env_var: str,
                  default: str = "on") -> bool:
    """Shared on/off knob parser: None reads ``env_var`` (falling back
    to ``default``); accepts bool or the CLI/env spellings
    on/off/1/0/true/false."""
    if spec is None:
        spec = os.environ.get(env_var, default)
    if isinstance(spec, bool):
        return spec
    s = str(spec).strip().lower()
    if s in ("on", "1", "true", "yes", ""):
        return True
    if s in ("off", "0", "false", "no"):
        return False
    raise ValueError(f"unparseable {env_var} value {spec!r}: "
                     "expected on/off")


def parse_prefix_cache(spec: Union[str, bool, None]) -> bool:
    """The ``prefix_cache`` knob: None reads ``HSTD_SERVE_PREFIX_CACHE``
    (default ON — templated traffic is the common case)."""
    return _parse_on_off(spec, ENV_PREFIX_CACHE)


def parse_timeline(spec: Union[str, bool, None]) -> bool:
    """The ``timeline`` knob (ISSUE 10): per-request lifecycle tracing
    — phase stamps, ``request_timeline`` events at finish/preempt, and
    the per-iteration ``iteration_ledger`` event. None reads
    ``HSTD_SERVE_TIMELINE`` (default ON — the stamps are host-side
    ``perf_counter`` reads, so the serving hot path mints zero new
    compiled variants either way); ``off`` makes the engine's telemetry
    byte-identical to the pre-tracing stream."""
    return _parse_on_off(spec, ENV_TIMELINE)


def parse_overlap(spec: Union[str, bool, None]) -> bool:
    """The ``overlap`` knob (ISSUE 12): dispatch-ahead decode — host
    scheduling runs concurrently with the in-flight device iteration,
    ``jax.device_get`` deferred by exactly one iteration. None reads
    ``HSTD_SERVE_OVERLAP`` (default ON — emitted tokens are identical
    either way); ``off`` restores the strictly serial
    schedule→dispatch→fetch→commit loop byte-for-byte, telemetry
    included."""
    return _parse_on_off(spec, ENV_OVERLAP)


def parse_swap(spec: Union[str, None]) -> str:
    """The KV spill-tier policy knob (ISSUE 17). ``off`` (the default)
    disables the host tier entirely — telemetry byte-identical to the
    pre-swap engine. ``never`` activates the tier for prefix DEMOTION
    only (preemption stays vLLM-recompute). ``always`` swaps every
    preemption victim to host (budget permitting); ``auto`` picks swap
    vs recompute per victim from the bytes-moved vs tokens-recomputed
    estimate. None reads ``HSTD_SERVE_SWAP``."""
    if spec is None:
        spec = os.environ.get(ENV_SWAP, "off")
    s = str(spec).strip().lower() or "off"
    if s not in ("auto", "always", "never", "off"):
        raise ValueError(f"unparseable {ENV_SWAP} value {spec!r}: "
                         "expected auto | always | never | off")
    return s


def parse_swap_bytes(spec: Union[str, int, None]) -> Optional[int]:
    """The host-tier byte budget (ISSUE 17): a non-negative int capping
    demoted payloads + swap reservations together, or None for
    unbounded. None reads ``HSTD_SERVE_SWAP_BYTES`` (empty/``0`` =
    unbounded — "no budget" is the safe default on a host whose RAM
    dwarfs the KV pool)."""
    if spec is None:
        spec = os.environ.get(ENV_SWAP_BYTES) or None
    if spec is None:
        return None
    try:
        n = int(str(spec).strip() or "0")
    except ValueError:
        raise ValueError(f"unparseable {ENV_SWAP_BYTES} value {spec!r}: "
                         "expected a byte count (0/empty = unbounded)")
    if n < 0:
        raise ValueError(f"{ENV_SWAP_BYTES} must be >= 0, got {n}")
    return n or None


def parse_gather_buckets(spec: Union[str, Sequence[int], None],
                         max_model_len: int, block_size: int) -> list[int]:
    """The gather-width ladder, shared by decode steps and prefill
    dispatches, from a knob value.

    ``spec`` is the comma-separated ``HSTD_SERVE_GATHER_BUCKETS`` form
    (``"512,2048"``), a sequence of ints, or None/``"auto"`` for the
    default ladder (quarter width + full width). ``"full"``/``"off"``
    disables bucketing (full-width gather only). Widths are rounded UP
    to a block multiple and clipped to ``max_model_len``, which is
    itself always present (the fallback bucket every admissible context
    fits). Returns the sorted ascending ladder."""
    if spec is None or (isinstance(spec, str)
                        and spec.strip().lower() in ("", "auto")):
        widths = [max_model_len // 4]
    elif isinstance(spec, str):
        s = spec.strip().lower()
        if s in ("full", "off", "0"):
            widths = []
        else:
            try:
                widths = [int(x) for x in spec.split(",") if x.strip()]
            except ValueError:
                raise ValueError(
                    f"unparseable {ENV_GATHER_BUCKETS} value {spec!r}: "
                    "expected comma-separated widths, 'auto', or 'full'")
    else:
        widths = [int(x) for x in spec]
    out = set()
    for w in widths:
        if w <= 0:
            continue
        out.add(min(max_model_len, -(-w // block_size) * block_size))
    out.add(max_model_len)
    return sorted(out)


class CachePlan(NamedTuple):
    """Static (hashable — it rides jit static_argnames) description of
    the model's flax cache pytree: the treedef plus, per flattened leaf,
    what it is — ``("kv", pool_index)`` for cached_key/cached_value
    (and, under ``kv_cache_dtype='int8'``, the ``cached_*_scale``
    fp32 scale planes, which ride parallel scale POOLS through the
    same gather/scatter/COW machinery), ``("latent", pool_index)`` for
    a latent-attention layer's ``cached_latent`` (ONE pool a layer, of
    ``(1, rank + rope)`` rows: the compressed row every head's keys and
    values are functions of; no K/V pair, and NO heads axis: the pool is
    ``[num_blocks, block_size, rank + rope]``, so that its two minor
    dims are a block's rows and not a degenerate ``1 x width`` plane the
    compiler has to re-lay out at every step (found in a rehearsal
    compile for the v5e, PR 28: two whole-pool copies a layer a step);
    it rides the same gather/scatter/COW/swap machinery), ``("index",)``
    for the per-row
    write indices, ``("scalar",)`` for model-level counters (unused
    under explicit position_ids), and ``("state", state_index)`` for what
    a recurrent (linear-attention) layer carries, ``recurrent_state`` /
    ``conv_state``: leaves indexed BY ROW and of a size that does not
    grow with the context, so no block table reaches them. The engine
    keeps one STATE POOL a leaf, ``[num_slots, ...]`` (row ``s`` is slot
    ``s``'s; a pad row of a prefill dispatch names row ``num_slots``,
    which is no row: it reads zeros and its write is dropped), sized from
    ``num_slots`` alone; ``state_shapes`` holds each one's ``(shape
    without the row axis, dtype name)``. No null row: a decode step takes
    the pools whole, and a ``[:num_slots]`` slice of a larger pool was a
    copy of the state a layer a step on the v5e (chip run, PR 33). A step
    hands every row its slot's state and writes it back; a prefix hit, a swap,
    a migration and a speculative rewind carry or roll back blocks only,
    so an engine with a ``state`` kind stands each of them down by name
    (``ServeEngine.__init__``). ``paths`` holds each leaf's key path
    so the PAGED cache (kernel mode) can be built as a nested dict with
    a ``block_tables`` sibling injected per attention scope — and the
    mutated pools re-extracted by NAME, immune to the flatten-order
    shift the extra leaf causes.

    ``kv_shardings`` (ISSUE 13) is one ``NamedSharding`` per KV POOL
    (pool-index order, empty for a single-device plan): each pool's
    heads axis over the mesh's ``tensor`` axis. It is how the in/out
    shardings reach the jitted step families — the engine places the
    pools with these at init (jit derives its in-shardings from the
    committed operands) and the steps re-pin their pool OUTPUTS to the
    same shardings, so the pools-chain can never drift off the mesh
    mid-serve. ``NamedSharding`` hashes by (mesh, spec), so a TP plan
    and a single-device plan over the same model are distinct static
    keys — each compiles its own executables, one per bucket, exactly
    like two engines over different models would."""

    treedef: Any
    kinds: tuple
    paths: tuple
    kv_shardings: tuple = ()
    state_shapes: tuple = ()


# the leaves a recurrent layer carries a row (``("state", i)`` kinds)
_STATE_LEAVES = ("recurrent_state", "conv_state")

# the kinds of cache leaf that live in a block pool
_POOLED = ("kv", "latent")


def _moe_counts(mut):
    """The routed layers' per-expert pair counts ``[expert layers, experts
    held]`` out of a step's mutated collections, in layer order; ``()``
    for a model that routes nothing (its step then returns exactly what
    it always did). A model whose residual path is hyper-connections sows
    a ``mhc_defect`` a wrap beside them (``models/xing4.py``): their
    maximum, one float a step, follows the counts; a model without the
    wrap returns the counts alone, as it always did."""
    stats = mut.get("moe_stats")
    if not stats:
        return ()
    leaves = jax.tree_util.tree_flatten_with_path(stats)[0]

    def named(name):
        return [(path, leaf) for path, leaf in leaves
                if any(getattr(p, "key", None) == name for p in path)]

    flat = named("expert_counts")
    defects = [leaf for _, leaf in named("mhc_defect")]

    def layer(path):
        name = next(str(getattr(p, "key", p)) for p in path
                    if str(getattr(p, "key", p)).startswith("layers_"))
        return int(name.rsplit("_", 1)[1])

    counts = jnp.stack([leaf for _, leaf in
                        sorted(flat, key=lambda pl: layer(pl[0]))])
    if defects:
        return counts, jnp.max(jnp.stack(defects))
    return (counts,)


def _routes(model) -> bool:
    """True for a model with DROPLESS routed experts (it takes a
    ``token_mask`` and sows ``moe_stats``); static under jit."""
    return bool(getattr(model.config, "n_routed_experts", 0))


def _masks_tokens(model, plan: CachePlan) -> bool:
    """True for a model whose apply takes ``token_mask`` (which tokens of
    the dispatch are real): routed experts count by it, recurrent layers
    hold their state still where it is False."""
    return _routes(model) or bool(plan.state_shapes)


def _constrain_pools(pools, plan: CachePlan):
    """Re-pin mutated pools to the plan's shardings (no-op for a
    single-device plan): the out-sharding half of the TP contract —
    scatter/gather propagation already keeps the heads axis sharded,
    but pinning makes it a stated invariant rather than an inference."""
    if not plan.kv_shardings:
        return pools
    return [lax.with_sharding_constraint(p, s)
            for p, s in zip(pools, plan.kv_shardings)]


# (model, max_ctx, mesh) -> (plan, pool_shapes): the cache structure is
# a function of the model config + width (+ the serving mesh, which
# only adds shardings), so engine rebuilds (bench's measured pass,
# server restarts) skip the eval_shape re-trace
_PLAN_CACHE: dict = {}


def build_cache_plan(model, params, max_ctx: int,
                     mesh=None) -> tuple[CachePlan, list]:
    """(plan, pool_shapes): traverse the cache collection's SHAPE (via
    ``jax.eval_shape`` — nothing is allocated) for a batch-1 decode at
    width ``max_ctx`` and classify every leaf. ``pool_shapes`` is one
    ``(heads, head_dim, dtype)`` per KV leaf in flatten order.

    With ``mesh`` (a tensor-parallel serving mesh, ISSUE 13) the plan
    additionally carries one ``NamedSharding`` per pool — heads over
    the ``tensor`` axis — and REJECTS loudly any pool whose kv-head
    count does not divide the tensor degree (GQA included: the check is
    on each cache leaf's own head count, which for GQA models is
    ``num_kv_heads``)."""
    key = (model, max_ctx, mesh)
    cached = _PLAN_CACHE.get(key)
    if cached is not None:
        return cached

    def init_cache(p):
        _, variables = model.apply(
            {"params": p}, jnp.ones((1, max_ctx), jnp.int32), decode=True,
            deterministic=True, mutable=["cache"])
        return variables["cache"]

    shapes = jax.eval_shape(init_cache, params)
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    kinds, pool_shapes, paths, state_shapes = [], [], [], []
    for path, leaf in flat:
        names = tuple(p.key if hasattr(p, "key") else str(p)
                      for p in path)
        name = names[-1]
        if name in ("cached_key", "cached_value",
                    "cached_key_scale", "cached_value_scale"):
            b, h, s, d = leaf.shape
            if s != max_ctx:
                raise ValueError(
                    f"cache leaf {name} has kv width {s}, expected "
                    f"{max_ctx} — non-slot-indexed cache layouts "
                    "(e.g. T5 encoder-decoder) are not serveable here")
            kinds.append(("kv", len(pool_shapes)))
            pool_shapes.append((h, d, leaf.dtype))
        elif name == "cached_latent":
            b, h, s, d = leaf.shape            # h == 1: no heads axis
            if s != max_ctx:
                raise ValueError(
                    f"cache leaf {name} has width {s}, expected {max_ctx}")
            kinds.append(("latent", len(pool_shapes)))
            pool_shapes.append((h, d, leaf.dtype))
        elif name in _STATE_LEAVES:
            kinds.append(("state", len(state_shapes)))
            state_shapes.append((tuple(int(d) for d in leaf.shape[1:]),
                                 str(leaf.dtype)))
        elif name == "cache_index":
            kinds.append(("index",))
        elif name == "position_index":
            kinds.append(("scalar",))
        else:
            raise ValueError(
                f"unsupported cache leaf {name!r}: the serve engine "
                "speaks the cached_key/cached_value (+ int8 scale), "
                "cached_latent and recurrent_state/conv_state protocols "
                "only")
        paths.append(names)
    if state_shapes and not pool_shapes:
        raise ValueError(
            "a model with recurrent state and no paged K/V or latent "
            "layer is not served: the scheduler counts a request's "
            "residency in blocks")
    kv_shardings: tuple = ()
    if mesh is not None and mesh.shape.get("tensor", 1) > 1:
        from huggingface_sagemaker_tensorflow_distributed_tpu.parallel.sharding import (
            kv_pool_sharding,
        )

        if state_shapes:
            raise ValueError(
                "recurrent state (recurrent_state / conv_state) cannot be "
                "served under a tensor-parallel mesh: a state pool "
                "sharded over its heads is not wired (ROADMAP R3)")
        if any(k[0] == "latent" for k in kinds):
            # a latent row has no heads axis to shard: every device
            # would need all of it (a replicated pool, and attention
            # sharded over the QUERY heads against it). Not wired:
            # refuse rather than shard the one "head" into nothing
            raise ValueError(
                "a latent-attention cache (cached_latent) cannot be "
                "served under a tensor-parallel mesh: its rows have no "
                "heads axis to shard, and a replicated pool is not "
                "wired (ROADMAP)")

        kv_shardings = tuple(kv_pool_sharding(mesh, h)
                             for h, _d, _dt in pool_shapes)
    result = (CachePlan(treedef, tuple(kinds), tuple(paths),
                        kv_shardings, tuple(state_shapes)), pool_shapes)
    _PLAN_CACHE[key] = result
    return result


def pool_dims(plan: CachePlan, pool_shapes, num_blocks: int,
              block_size: int) -> list:
    """The shape of every block pool of ``plan``: ``[num_blocks,
    block_size, heads, head_dim]`` for a K/V (or scale) pool,
    ``[num_blocks, block_size, width]`` for a latent pool."""
    latent = {k[1] for k in plan.kinds if k[0] == "latent"}
    return [(num_blocks, block_size, d) if i in latent
            else (num_blocks, block_size, h, d)
            for i, (h, d, _dt) in enumerate(pool_shapes)]


def _pool_view(pool):
    """A pool as ``[num_blocks, block_size, heads, dim]``: a latent pool
    gains a heads axis of one (a reshape, no copy)."""
    return pool[:, :, None, :] if pool.ndim == 3 else pool


def _pool_rows(pool, rows):
    """``rows`` ``[n, heads, dim]`` as the pool stores them: a latent
    pool's rows have no heads axis."""
    return rows[:, 0, :] if pool.ndim == 3 else rows


def _assemble_cache(plan: CachePlan, pools, block_tables, context_lens,
                    width: Optional[int] = None, state_rows=()):
    """The model-facing cache pytree: contiguous per-slot KV gathered
    from the pools (restricted to the static ``width`` bucket when
    given), write indices set to each slot's context length, and each
    recurrent leaf's rows as ``state_rows`` has them (one ``[rows, ...]``
    array a ``state`` kind, by its index)."""
    leaves = []
    for kind in plan.kinds:
        if kind[0] in _POOLED:
            leaves.append(gather_paged_kv(_pool_view(pools[kind[1]]),
                                          block_tables, width=width))
        elif kind[0] == "state":
            leaves.append(state_rows[kind[1]])
        elif kind[0] == "index":
            leaves.append(context_lens.astype(jnp.int32))
        else:
            leaves.append(jnp.zeros((), jnp.int32))
    return jax.tree_util.tree_unflatten(plan.treedef, leaves)


def _pick_token(logits, sampled: bool, temps, top_ks, top_ps, keys, folds):
    """The next token of each row of ``logits`` ``[rows, vocab]``: greedy
    argmax, or the per-slot seeded sample for rows with ``temperature >
    0`` when the (static) ``sampled`` mode is on. Under the program's own
    scope ``serve/sample`` (``obs/programs.py``)."""
    with jax.named_scope("serve/sample"):
        logits = logits.astype(jnp.float32)
        if sampled:
            return sample_per_slot(logits, temps, top_ks, top_ps, keys, folds)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)


def _decode_step(model, params, pools, tokens, block_tables, context_lens,
                 active, temps, top_ks, top_ps, keys, folds,
                 plan: CachePlan, width: int, sampled: bool, states=()):
    """One decode iteration over ALL slots (static [S] shapes): feed
    each slot's last token against a ``width``-bucket gathered cache,
    write its K/V at ``context_len`` (scattered back to the pools;
    inactive slots write the reserved null block 0), return the next
    token per slot — greedy argmax, or the per-slot seeded sample for
    rows with ``temperature > 0`` when the (static) ``sampled`` mode is
    on. Callers guarantee ``context_len + 1 <= width`` for every active
    slot. Returns ``(next_tok, pools)``; a model with routed experts adds
    a third, ``[expert layers, experts held]`` int32: the pairs each held
    expert got from the ACTIVE slots. ``states`` (the state pools of a
    plan with ``state`` kinds, row ``s`` slot ``s``'s) come back last, as
    one list: an active slot's rows advanced by the token, every other
    row as it was."""
    with jax.named_scope("serve/cache_read"):
        cache = _assemble_cache(plan, pools, block_tables, context_lens,
                                width=width, state_rows=states)
    # kv-buffer validity includes the slot being written this step —
    # exactly generate_causal's decode-step mask, at bucket width
    valid = (jnp.arange(width)[None, :]
             <= context_lens[:, None]).astype(jnp.int32)
    routes = _routes(model)
    logits, mut = model.apply(
        {"params": params, "cache": cache}, tokens[:, None], valid,
        position_ids=context_lens[:, None], decode=True,
        deterministic=True,
        mutable=["cache", "moe_stats"] if routes else ["cache"],
        **({"token_mask": active[:, None]}
           if _masks_tokens(model, plan) else {}))
    next_tok = _pick_token(logits[:, -1, :], sampled, temps, top_ks, top_ps,
                           keys, folds)
    # scatter the step's writes back; inactive slots route to the null
    # block so the scatter itself needs no masking
    mut_leaves = jax.tree_util.tree_leaves(mut["cache"])
    new_pools, new_states = list(pools), list(states)
    with jax.named_scope("serve/cache_write"):
        safe_tables = jnp.where(active[:, None], block_tables, 0)
        pos = jnp.where(active, context_lens, 0)
        for leaf, kind in zip(mut_leaves, plan.kinds):
            if kind[0] == "state":
                # the model held an inactive row's state still (token_mask)
                new_states[kind[1]] = leaf
            if kind[0] not in _POOLED:
                continue
            written = jnp.take_along_axis(
                leaf, pos[:, None, None, None], axis=2)[:, :, 0, :]  # [S, H, D]
            new_pools[kind[1]] = scatter_paged_kv(
                new_pools[kind[1]], safe_tables, pos,
                _pool_rows(new_pools[kind[1]], written))
    return (next_tok, _constrain_pools(new_pools, plan),
            *_moe_counts(mut), *((new_states,) if states else ()))


def _paged_cache(plan: CachePlan, pools, block_tables, context_lens,
                 state_rows=()):
    """The model-facing PAGED cache pytree (kernel mode): every K/V or
    latent leaf is its whole block pool (no gather — the model's fused
    kernel walks the tables in-attention), write indices are the context
    lengths, and a ``block_tables`` leaf rides next to each attention
    scope's ``cache_index`` (the marker the model's paged decode branch
    keys on). Built as a nested dict from the plan's recorded paths — the
    treedef can't be reused because of the injected sibling."""
    root: dict = {}
    for path, kind in zip(plan.paths, plan.kinds):
        node = root
        for key in path[:-1]:
            node = node.setdefault(key, {})
        if kind[0] in _POOLED:
            node[path[-1]] = pools[kind[1]]
        elif kind[0] == "state":
            node[path[-1]] = state_rows[kind[1]]
        elif kind[0] == "index":
            node[path[-1]] = context_lens.astype(jnp.int32)
            node["block_tables"] = block_tables
        else:
            node[path[-1]] = jnp.zeros((), jnp.int32)
    return root


def _paged_decode_step(model, params, pools, tokens, block_tables,
                       context_lens, active, temps, top_ks, top_ps, keys,
                       folds, plan: CachePlan, width: int, sampled: bool,
                       states=()):
    """One FUSED decode iteration over all slots (kernel mode): the
    model's paged decode branch scatters each slot's new K/V (or latent
    row) straight into the pools and attends via its Pallas paged kernel
    — no dense [S, H, width, D] intermediate is ever materialized.
    ``width`` restricts the block-table walk to the iteration's gather
    bucket (same ladder, same compile-per-bucket contract as the XLA
    path); inactive slots route writes to null block 0 at context 0.
    Returns what :func:`_decode_step` returns, in the same positions: the
    routed counts of a model with routed experts, ``states`` last."""
    bs = pools[0].shape[1]
    tables = block_tables[:, :width // bs]
    safe_tables = jnp.where(active[:, None], tables, 0)
    ctx = jnp.where(active, context_lens, 0)
    cache = _paged_cache(plan, pools, safe_tables, ctx, states)
    logits, mut = model.apply(
        {"params": params, "cache": cache}, tokens[:, None], None,
        position_ids=ctx[:, None], decode=True, deterministic=True,
        mutable=["cache", "moe_stats"] if _routes(model) else ["cache"],
        **({"token_mask": active[:, None]}
           if _masks_tokens(model, plan) else {}))
    next_tok = _pick_token(logits[:, -1, :], sampled, temps, top_ks, top_ps,
                           keys, folds)
    # the model scattered into the pools in place (cache mutation);
    # re-extract them BY PATH — the block_tables sibling shifts the
    # flatten order, so positional zip against plan.kinds would skew
    flat, _ = jax.tree_util.tree_flatten_with_path(mut["cache"])
    by_path = {tuple(p.key if hasattr(p, "key") else str(p)
                     for p in path): leaf for path, leaf in flat}
    new_pools, new_states = list(pools), list(states)
    for path, kind in zip(plan.paths, plan.kinds):
        if kind[0] in _POOLED:
            new_pools[kind[1]] = by_path[path]
        elif kind[0] == "state":
            new_states[kind[1]] = by_path[path]
    return (next_tok, new_pools, *_moe_counts(mut),
            *((new_states,) if states else ()))


def prefill_write_path(chunk: int, block_size: int) -> str:
    """How a prefill dispatch puts its chunks' K/V (or latent rows) back
    into the pools: ``"pages"``, one update a WHOLE block
    (``ops.attention.scatter_paged_blocks``), where the chunk is a multiple
    of the block size: ``start`` is on the chunk grid (the scheduler's
    ``prefill_pos``), so a row's chunk is exactly ``chunk // block_size``
    blocks of its table; ``"rows"``, one update a token
    (``scatter_paged_kv``), for any other chunk. Static at trace time; it
    is ``write_path`` on every ``serve/prefill_chunk`` span and in
    ``stats()``."""
    return "pages" if chunk % block_size == 0 else "rows"


def _prefill_chunk(model, params, pools, chunks, block_tables, start, rel,
                   temps, top_ks, top_ps, keys, folds, plan: CachePlan,
                   sampled: bool, width: Optional[int] = None, states=(),
                   state_rows=None):
    """One BATCHED prefill dispatch: up to G prefilling slots' chunks as
    G independent rows (static [G, C] shape; unused rows carry pad
    tokens against the null block table). Each row writes its chunk's
    K/V into its own blocks starting at ``start[g]`` (as WHOLE blocks, one
    update each, where the chunk is a multiple of the block size; a row a
    token otherwise: :func:`prefill_write_path`) and returns the
    token after prompt position ``rel[g]`` (chunk-relative index of the
    last REAL prompt token; meaningful on a final chunk only — other
    rows return a discarded value). Isolation between the packed
    requests is structural: row g's attention reads exactly the KV its
    own block table gathers, so no mask can leak another request's
    context into it.

    ``width`` (a STATIC python int, multiple of the block size; None =
    the table's full span) is the gather bucket: the chunk attends the
    first ``width`` logical positions of each row and no more. Keys at
    ``>= start + C`` are masked at any width, so a narrower bucket
    drops only terms that are exactly zero. Callers guarantee
    ``start + C <= width`` for every row; the write-back goes through
    the full tables either way. Returns ``(next_tok, pools)``, and for a
    model with routed experts a third, the REAL tokens' pair counts per
    held expert (as :func:`_decode_step`).

    ``states`` / ``state_rows`` (a plan with ``state`` kinds): row g reads
    and writes row ``state_rows[g]`` of every state pool, its slot's (a
    pad row names ``num_slots``, no row of the pool: it reads zeros and
    its write is dropped). A row whose chunk starts at 0 starts from
    ZEROS, whatever the slot's last request left there: slot reuse needs
    no clearing pass on the host. The pad tail of a final chunk and a pad
    row do not advance the state (the model's ``token_mask``). The state
    pools come back last, as one list."""
    G, C = chunks.shape
    bs = pools[0].shape[1]
    max_ctx = block_tables.shape[1] * bs if width is None else width
    fresh = start == 0
    with jax.named_scope("serve/cache_read"):
        cache = _assemble_cache(
            plan, pools, block_tables, start, width=width,
            state_rows=[jnp.where(fresh.reshape((G,) + (1,) * (st.ndim - 1)),
                                  jnp.zeros((), st.dtype),
                                  st.at[state_rows].get(mode="fill",
                                                        fill_value=0))
                        for st in states])
    # chunk slots are marked valid; the model's step mask
    # (key_slot <= cache_index + q_index) imposes causality within the
    # chunk, and pad-tail keys sit AFTER every real query so they are
    # never attended. Pad-tail writes land in block space the scheduler
    # trims back after the final chunk.
    valid = (jnp.arange(max_ctx)[None, :]
             < start[:, None] + C).astype(jnp.int32)
    pos_ids = start[:, None] + jnp.arange(C, dtype=jnp.int32)[None, :]
    routes = _routes(model)
    extra = {}
    if _masks_tokens(model, plan):
        # the real tokens of the dispatch, for the routed layers' counts
        # and the recurrent layers' state:
        # a pad row rides the null block table, a final chunk is real up
        # to ``rel``, any other chunk is real whole
        n_real = jnp.where(rel >= 0, rel + 1, C) * (block_tables[:, 0] != 0)
        extra["token_mask"] = jnp.arange(C)[None, :] < n_real[:, None]
    last = jnp.clip(rel, 0, C - 1)
    picks = getattr(model, "takes_logit_positions", False)
    if picks:
        # the model runs its head on the one row a chunk needs
        extra["logit_positions"] = last
    logits, mut = model.apply(
        {"params": params, "cache": cache}, chunks, valid,
        position_ids=pos_ids, decode=True, deterministic=True,
        mutable=["cache", "moe_stats"] if routes else ["cache"], **extra)
    with jax.named_scope("serve/sample"):
        if picks:
            sel = logits[:, 0]                                 # [G, V]
        else:
            sel = jnp.take_along_axis(
                logits.astype(jnp.float32), last[:, None, None],
                axis=1)[:, 0]                                  # [G, V]
    next_tok = _pick_token(sel, sampled, temps, top_ks, top_ps, keys,
                           folds)                              # [G]
    mut_leaves = jax.tree_util.tree_leaves(mut["cache"])
    new_pools, new_states = list(pools), list(states)
    by_pages = prefill_write_path(C, bs) == "pages"
    with jax.named_scope("serve/cache_write"):
        if not by_pages:
            positions = (start[:, None] + jnp.arange(
                C, dtype=jnp.int32)[None, :]).reshape(-1)
            tables_tok = jnp.repeat(block_tables, C, axis=0)   # [G*C, nb]
        for leaf, kind in zip(mut_leaves, plan.kinds):
            if kind[0] == "state":
                new_states[kind[1]] = states[kind[1]].at[state_rows].set(
                    leaf, mode="drop")
            if kind[0] not in _POOLED:
                continue
            h, d = leaf.shape[1], leaf.shape[3]
            written = jax.vmap(
                lambda row, s: lax.dynamic_slice(row, (0, s, 0), (h, C, d))
            )(leaf, start)                                      # [G, H, C, D]
            pool = new_pools[kind[1]]
            if by_pages:
                new_pools[kind[1]] = scatter_paged_blocks(
                    pool, block_tables, start, written)
            else:
                rows = written.transpose(0, 2, 1, 3).reshape(G * C, h, d)
                new_pools[kind[1]] = scatter_paged_kv(
                    pool, tables_tok, positions, _pool_rows(pool, rows))
    return (next_tok, _constrain_pools(new_pools, plan),
            *_moe_counts(mut), *((new_states,) if states else ()))


@functools.lru_cache(maxsize=2)
def _decode_step_jit(donate: bool):
    """Process-wide jitted decode step (one per donation mode).
    ``model``/``plan``/``width``/``sampled`` are static — each gather
    bucket (and each sampling mode actually used) compiles exactly
    once; pools are donated on accelerator backends so the scatter
    updates them in place (CPU has no donation and would warn every
    call)."""
    return jax.jit(_decode_step, static_argnums=(0, 12, 13, 14),
                   donate_argnums=(2, 15) if donate else ())


@functools.lru_cache(maxsize=2)
def _prefill_chunk_jit(donate: bool):
    """Process-wide jitted prefill dispatch: one compile per (model,
    plan, sampled, width) and row count."""
    return jax.jit(_prefill_chunk, static_argnums=(0, 12, 13, 14),
                   donate_argnums=(2, 15) if donate else ())


@functools.lru_cache(maxsize=2)
def _paged_decode_step_jit(donate: bool):
    """Process-wide jitted FUSED decode step (kernel mode) — same
    static/donation contract as :func:`_decode_step_jit`: one compile
    per (model, plan, bucket, sampled)."""
    return jax.jit(_paged_decode_step, static_argnums=(0, 12, 13, 14),
                   donate_argnums=(2, 15) if donate else ())


def _copy_block(pools, src, dst):
    """Copy-on-write device op: duplicate physical block ``src`` into
    ``dst`` across every pool of one model's KV address space. Scalar
    src/dst are traced, so ONE compile covers every COW a pool
    geometry ever performs (fixed shape — the compile-flatness gates
    stay honest on the cache-hit path). Under a tensor-parallel mesh
    the copy is shard-local by construction: the pools are sharded on
    their heads axis and the copy addresses only the (replicated)
    block axis, so each device duplicates its own head slice — output
    sharding propagates from the pool operand, no collective, and the
    one-compile contract holds per sharding like any other step."""
    return [p.at[dst].set(p[src]) for p in pools]


@functools.lru_cache(maxsize=2)
def _copy_block_jit(donate: bool):
    # graftlint: allow[R3] no static key by design: pools are traced arrays and src/dst are traced scalars, so ONE compile covers every COW a pool geometry performs
    return jax.jit(_copy_block, donate_argnums=(0,) if donate else ())


def _mesh_context(mesh):
    """``use_mesh(mesh)``, or nothing to enter without a mesh."""
    from huggingface_sagemaker_tensorflow_distributed_tpu.parallel.mesh import (
        use_mesh,
    )

    return use_mesh(mesh) if mesh is not None else contextlib.nullcontext()


# the parts of an iteration's wall time: indices of `_iter_parts` and,
# with the iteration's length and the gap before it, of `_host_totals`
_STAGE, _DISPATCH, _FETCH, _COMMIT, _DUR, _GAP = range(6)
HOST_LOOP_FIELDS = ("stage_s", "dispatch_s", "fetch_wait_s", "commit_s",
                    "dur_s", "gap_s")


class _PendingDecode(NamedTuple):
    """One in-flight PLAIN decode dispatch (dispatch-ahead pipeline,
    ISSUE 12): the un-fetched device next-token array, the (slot,
    request) pairs that rode it (captured at dispatch — a rider's slot
    may be reassigned by the time a wasted token is discarded), the
    bucket it ran at, and the dispatch-enqueue cost/stamp. The fetch is
    deferred to the NEXT engine iteration: everything the host does in
    between runs concurrently with this dispatch's device compute.
    ``moe_seq``: how many routed-count entries the run had made once
    this dispatch had added its own (``_moe_mark``: an absolute count,
    which entries resolved meanwhile do not shift): its fetch shows them
    all computed."""

    nxt: Any
    riders: tuple
    bucket: int
    dispatch_s: float
    t_dispatch: float
    moe_seq: int = 0


class _PendingSpec(NamedTuple):
    """One in-flight SPECULATIVE window (dispatch-ahead, ISSUE 12).
    Unlike the plain pipeline, a window's commit must complete before
    the next window dispatches (the next window's input token and
    context advance are data-dependent on the acceptance counts), so
    the overlap window covers the NEXT iteration's admission, prefill
    dispatches, and telemetry — not the next decode dispatch."""

    drafts: Any
    n_acc: Any
    bonus: Any
    riders: tuple
    bucket: int
    dispatch_s: float
    t_dispatch: float


def _scatter_window(pools, plan: CachePlan, cache_leaves, block_tables,
                    context_lens, active, k: int):
    """Scatter a just-computed (k+1)-token window's K/V — written by a
    model apply into an assembled (contiguous, bucket-width) cache at
    slots ``context_lens .. context_lens + k`` per row — back into the
    paged pools. Inactive rows route to the reserved null block 0 so
    the write path needs no masking (the plain decode step's
    convention, widened to the window)."""
    S = context_lens.shape[0]
    safe_tables = jnp.where(active[:, None], block_tables, 0)
    safe_start = jnp.where(active, context_lens, 0)
    flat_pos = (safe_start[:, None]
                + jnp.arange(k + 1, dtype=jnp.int32)[None]).reshape(-1)
    tables_tok = jnp.repeat(safe_tables, k + 1, axis=0)   # [S*(k+1), nb]
    new_pools = list(pools)
    for leaf, kind in zip(cache_leaves, plan.kinds):
        if kind[0] not in _POOLED:
            continue
        h, d = leaf.shape[1], leaf.shape[3]
        written = jax.vmap(
            lambda row, s: lax.dynamic_slice(row, (0, s, 0), (h, k + 1, d))
        )(leaf, safe_start)                               # [S, H, k+1, D]
        written = written.transpose(0, 2, 1, 3).reshape(S * (k + 1), h, d)
        new_pools[kind[1]] = scatter_paged_kv(
            new_pools[kind[1]], tables_tok, flat_pos, written)
    return _constrain_pools(new_pools, plan)


def _spec_decode_step(model, params, draft_model, draft_params, t_pools,
                      d_pools, tokens, block_tables, context_lens, active,
                      temps, top_ks, top_ps, keys, folds, t_plan: CachePlan,
                      d_plan: CachePlan, width: int, k: int, sampled: bool):
    """One SPECULATIVE decode iteration over all slots (static [S]
    shapes): the draft proposes ``k`` tokens per slot autoregressively
    against its own paged pools, then ONE width-(k+1) verify dispatch of
    the target scores every window position — structurally just a wider
    bucketed decode, so it rides the same ``width`` gather ladder. Per
    row the accepted prefix + bonus token come back for the host to
    commit; rejected draft tokens leave only stale K/V past the
    committed context, which the host rewinds in O(1) by NOT advancing
    ``context_lens`` over them (validity masks are context-derived, so
    stale slots are invisible and the next window overwrites them).

    ``tokens`` is each slot's newest COMMITTED token (its K/V lands at
    ``context_lens`` during the verify, exactly like the plain step);
    ``folds`` is the window's starting request-global token index — the
    per-row PRNG key for the whole window derives from (request seed,
    window start) alone, which is what keeps sampled speculative
    streams bitwise-reproducible across recompute preemption (windows
    re-start at the same committed index, so the same keys re-derive).
    Greedy rows accept by longest argmax-matching prefix
    (:func:`~..models.generate.speculative_accept_greedy` — token-exact
    vs ``generate_causal``); sampled rows use Leviathan rejection
    acceptance on the per-slot WARPED distributions, so the emitted
    marginal is the target's.

    Returns ``(drafts [S, k], n_acc [S], bonus [S], t_pools, d_pools)``.
    Callers guarantee ``context_lens + k + 1 <= width`` per active
    slot."""
    S = tokens.shape[0]
    pos_grid = jnp.arange(width)[None, :]
    win_pos = (context_lens[:, None]
               + jnp.arange(k + 1, dtype=jnp.int32)[None])   # [S, k+1]
    if sampled:
        # window key = f(request seed, window start): split into the
        # draft-proposal stream and the acceptance stream
        wkeys = jax.vmap(jax.random.fold_in)(keys, folds)
        pair = jax.vmap(lambda kk: jax.random.split(kk, 2))(wkeys)
        draft_keys, accept_keys = pair[:, 0], pair[:, 1]
    else:
        draft_keys = keys

    # -- draft: k+1 single-token steps over ONE pre-assembled bucket
    #    cache (the step writes stay inside the carried pytree — no
    #    per-step pool gather/scatter; the final carry holds the whole
    #    window's K/V, scattered back once below). Step k's output is
    #    discarded: it only exists so the final carry contains
    #    d_{k-1}'s K/V, which the NEXT window's draft needs resident
    #    when the full window is accepted.
    d_cache = _assemble_cache(d_plan, d_pools, block_tables, context_lens,
                              width=width)

    def dstep(carry, t):
        tok, cache = carry
        valid = (pos_grid <= (context_lens + t)[:, None]).astype(jnp.int32)
        lg, mut = draft_model.apply(
            {"params": draft_params, "cache": cache}, tok[:, None], valid,
            position_ids=(context_lens + t)[:, None], decode=True,
            deterministic=True, mutable=["cache"])
        lg = lg[:, -1, :].astype(jnp.float32)
        if sampled:
            nxt = sample_per_slot(lg, temps, top_ks, top_ps, draft_keys,
                                  jnp.full((S,), t, jnp.int32))
            qp = jax.nn.softmax(
                warp_logits_per_slot(lg, temps, top_ks, top_ps), axis=-1)
            return (nxt, mut["cache"]), (nxt, qp)
        nxt = jnp.argmax(lg, axis=-1).astype(jnp.int32)
        return (nxt, mut["cache"]), nxt

    (_, d_final), ys = lax.scan(dstep, (tokens, d_cache),
                                jnp.arange(k + 1))
    if sampled:
        drafts = ys[0][:k].T                                 # [S, k]
        q_probs = jnp.swapaxes(ys[1], 0, 1)[:, :k]           # [S, k, V]
    else:
        drafts = ys[:k].T
    new_d_pools = _scatter_window(d_pools, d_plan,
                                  jax.tree_util.tree_leaves(d_final),
                                  block_tables, context_lens, active, k)

    # -- verify: ONE (k+1)-wide target pass scores the whole window and
    #    writes its K/V (accepted slots become resident; rejected ones
    #    are the stale tail the host's context rewind hides)
    verify_in = jnp.concatenate([tokens[:, None], drafts], axis=1)
    t_cache = _assemble_cache(t_plan, t_pools, block_tables, context_lens,
                              width=width)
    valid = (pos_grid <= (context_lens + k)[:, None]).astype(jnp.int32)
    logits, mut = model.apply(
        {"params": params, "cache": t_cache}, verify_in, valid,
        position_ids=win_pos, decode=True, deterministic=True,
        mutable=["cache"])
    lg = logits.astype(jnp.float32)                          # [S, k+1, V]
    t_pred = jnp.argmax(lg, axis=-1).astype(jnp.int32)
    n_acc, bonus = speculative_accept_greedy(t_pred, drafts)
    if sampled:
        p_probs = jax.nn.softmax(jax.vmap(
            lambda x: warp_logits_per_slot(x, temps, top_ks, top_ps),
            in_axes=1, out_axes=1)(lg), axis=-1)
        n_acc_s, nxt_s = jax.vmap(_speculative_accept)(
            p_probs, q_probs, drafts, accept_keys)
        on = temps > 0
        n_acc = jnp.where(on, n_acc_s, n_acc)
        bonus = jnp.where(on, nxt_s, bonus)
    new_t_pools = _scatter_window(t_pools, t_plan,
                                  jax.tree_util.tree_leaves(mut["cache"]),
                                  block_tables, context_lens, active, k)
    return drafts, n_acc, bonus, new_t_pools, new_d_pools


@functools.lru_cache(maxsize=2)
def _spec_step_jit(donate: bool):
    """Process-wide jitted speculative step (one per donation mode):
    ``model``/``draft_model``/plans/``width``/``k``/``sampled`` are
    static, so each gather bucket (per sampling mode actually used)
    compiles exactly once and a rebuilt engine over the same
    model/geometry reuses the executables."""
    return jax.jit(_spec_decode_step,
                   static_argnums=(0, 2, 15, 16, 17, 18, 19),
                   donate_argnums=(4, 5) if donate else ())


class EngineStats(NamedTuple):
    decode_steps: int
    prefill_chunks: int
    prefill_dispatches: int
    # Σ over real prefill rows of start + chunk, and Σ over dispatches
    # of rows dispatched × bucket width (ISSUE 26): needed ÷ attended
    # is how full the prefill buckets ran
    prefill_keys_needed: int
    prefill_keys_attended: int
    tokens_generated: int
    decode_tokens: int
    decode_time_s: float
    preemptions: int
    bucket_switches: int
    kv_peak_utilization: float
    kv_utilization: float
    gather_waste_peak: float
    gather_waste_mean: float
    draft_proposed: int = 0
    draft_accepted: int = 0
    acceptance_rate: Optional[float] = None
    spec_windows: int = 0
    verify_waste_peak: float = 0.0
    verify_waste_mean: float = 0.0
    # prefix caching (ISSUE 8)
    # the option, or "off (recurrent state)" where the index stands down
    prefix_cache: Union[bool, str] = False
    prefix_cached_tokens: int = 0
    cache_hit_rate: Optional[float] = None
    blocks_shared_peak: int = 0
    blocks_saved_peak: int = 0
    cow_copies: int = 0
    prefix_evictions: int = 0
    shared_read_frac: float = 0.0
    peak_resident_requests: int = 0
    # paged-attention kernel + int8 pools (ISSUE 9)
    kernel: str = "xla"
    # which way decode steps attended, and how many went each way (a
    # speculative engine's windows are gather steps whatever ``kernel``)
    decode_path: str = "gather"
    decode_steps_by_path: Optional[dict] = None
    # how prefill dispatches write their chunks back (ISSUE 38): "pages",
    # one update a whole block, or "rows", one a token
    write_path: str = "rows"
    kv_dtype: str = "fp"
    kv_bytes_read: int = 0
    kv_token_bytes: int = 0
    # dispatch-ahead pipeline (ISSUE 12)
    overlap: bool = False
    overlap_flushes: int = 0
    # tensor-parallel serving (ISSUE 13): the mesh degree and the KV
    # pool's per-device footprint (num_blocks × per-device block
    # bytes — kv_token_bytes above is already per-device under TP)
    tp: int = 1
    kv_pool_bytes_per_device: int = 0
    # host-RAM KV spill tier (ISSUE 17): swap-mode preemption +
    # prefix demotion. All zero/"off" when the tier is disabled.
    swap_policy: str = "off"
    swap_outs: int = 0
    swap_ins: int = 0
    swap_bytes: int = 0
    restore_s: float = 0.0
    recompute_tokens_avoided: int = 0
    host_tier_hits: int = 0
    host_tier_hit_rate: Optional[float] = None
    # cross-engine KV transport (ISSUE 18): migration traffic through
    # this engine — all zero unless migrate_request touched it
    migrations_in: int = 0
    migrations_out: int = 0
    migration_bytes: int = 0
    # latent-attention cache and routed experts (ISSUE 28): bytes a
    # token's latent rows cost over all layers (None: a K/V cache), and
    # the routed pairs counted under a telemetry sink (0 without one:
    # an untraced run fetches no count)
    latent_bytes_per_token: Optional[int] = None
    moe_pairs: int = 0
    moe_pairs_held: int = 0
    # a model whose residual path is hyper-connections under its own gate
    # (ISSUE 35; what the model's ``residual_kw`` says): streams a token
    # and the gate's name, None for any other model
    residual_streams: Optional[int] = None
    gate: Optional[str] = None
    # how the prefill dispatches of a latent-attention model attended
    # (ISSUE 32): dispatches by the model's ``expanded_form`` (None: a
    # K/V cache)
    prefill_dispatches_by_form: Optional[dict] = None
    # recurrent state (ISSUE 33), None for a model without it: bytes a
    # slot's state costs over all recurrent layers (it never grows), the
    # state pools' bytes (num_slots of them), and the most slots that held
    # a request at once
    state_bytes_per_slot: Optional[int] = None
    state_pool_bytes: Optional[int] = None
    state_slots_peak: Optional[int] = None
    # how its one-token steps advance the state (ISSUE 36): the model's
    # ``state_step``, ``kernel`` | ``xla``
    state_step: Optional[str] = None
    # and its prefill dispatches: the model's ``state_chunk``,
    # ``kernel`` | ``xla``
    state_chunk: Optional[str] = None


class ServeEngine:
    """Continuous-batching engine for the decoder-only families that
    follow the slot-indexed KV-cache protocol (GPT-2, dense Llama).

    ``num_blocks`` includes the reserved null block: allocatable KV is
    ``(num_blocks - 1) * block_size`` tokens, shared by every request —
    size it for the expected CONCURRENT context, not
    ``num_slots × max_model_len``.

    ``gather_buckets`` is the gather-width ladder of decode steps and
    prefill dispatches alike (None reads ``HSTD_SERVE_GATHER_BUCKETS``,
    default quarter + full width; pass ``[max_model_len]`` or
    ``"full"`` to force full-width gather).
    ``prefill_batch`` caps how many prefilling slots' chunks one
    prefill dispatch packs (clamped to ``num_slots``).

    ``speculate_k > 0`` turns on SPECULATIVE decode (None reads
    ``HSTD_SERVE_SPECULATE_K``, default off): per iteration a draft
    model proposes ``k`` tokens per running slot and one width-(k+1)
    verify dispatch of the target scores them all — acceptance-rate ×
    (k+1) tokens land per decode step without changing the output
    (greedy stays token-exact vs ``generate_causal``; sampled rows keep
    the Leviathan rejection acceptance, so the emitted distribution is
    the target's). ``draft`` selects the proposer: a
    ``(draft_model, draft_params)`` tuple, an int = build a layer-skip
    self-draft from the target's own first N layers
    (``models.generate.self_draft`` — no second checkpoint), or None =
    ``HSTD_SERVE_DRAFT_LAYERS`` falling back to a quarter of the
    target's layers. Requests additionally reserve the verify window:
    ``prompt + max_new_tokens + speculate_k`` must fit
    ``max_model_len``.

    ``prefix_cache`` (None reads ``HSTD_SERVE_PREFIX_CACHE``, default
    on) turns on copy-on-write prefix caching: full block-aligned
    prompt-prefix chunks are indexed by a rolling hash chain, identical
    prefixes across requests map onto SHARED read-only KV blocks
    (refcounted, charged to the pool once), and prefill for a cache hit
    starts at the first uncached chunk — TTFT for templated traffic
    collapses toward the tail's prefill plus a block-table write, and
    effective KV capacity multiplies by the dedup factor. Blocks of
    finished requests stay cached (zero-ref LRU) until pool pressure
    evicts them, oldest first. Output is token-exact vs a cold start:
    cached KV is bitwise what this request's own prefill would have
    produced, and a scatter into a still-shared block (the chunk-grid
    overlap at admission) is privatized by a device-side block copy
    first (:func:`_copy_block`). ``prefix_cache='off'`` is
    byte-for-byte the refcount-free engine's behavior — same tokens,
    same compile count.

    ``kernel`` (None reads ``HSTD_SERVE_KERNEL``; unset, the engine
    chooses) selects the decode-attention path: ``xla`` gathers a dense
    view then attends (reference, CPU-native), ``pallas`` runs the fused
    paged-decode kernel — gather folded into the attention read, int8
    dequant in-tile, no page outside the context or the sliding band
    read. Left to choose (:func:`resolve_decode_path`), the engine takes
    a kernel on a TPU without a mesh for pools in a floating type (K/V
    pools of 128-wide heads; latent pools, through the model's own
    absorbed-form kernel), and the gather path anywhere else; the path
    taken is ``decode_path`` in ``stats()``, the ``report`` event and
    the ``serve/decode_step`` span's arguments. Speculative
    engines keep draft/verify on the assembled path either way (the
    kernels are single-token). A latent-attention model chooses for its
    own prefill chunks (its ``expanded_form``: a fused kernel on a TPU,
    an XLA loop elsewhere); the engine writes the answer beside
    ``latent_path`` on each ``serve/prefill_chunk`` span and counts the
    dispatches by form (``prefill_dispatches_by_form`` in ``stats()``,
    ``slo_summary()`` and the ``report`` event). Every prefill dispatch
    writes its chunks back as whole blocks where ``prefill_chunk`` is a
    multiple of ``block_size`` (:func:`prefill_write_path`: ``write_path``
    beside ``latent_path`` on the span, and in ``stats()`` and the
    ``report`` event beside ``decode_path``). ``kv_cache_dtype`` (None reads
    ``HSTD_SERVE_KV_DTYPE``, default = the model config's own value)
    selects pool storage; ``int8`` rebuilds the serving module around
    ``kv_cache_dtype='int8'`` (params untouched) and the exactness
    contract moves to ``generate_causal`` on that same config.
    ``kv_pool_bytes`` sizes ``num_blocks`` from a KV memory budget
    (``1 + budget // block_bytes``) instead of a block count.

    ``timeline`` (None reads ``HSTD_SERVE_TIMELINE``, default on)
    turns on per-request lifecycle tracing: ``request_timeline`` +
    ``iteration_ledger`` telemetry events from host-side phase stamps
    (zero new compiled variants; ``off`` restores the pre-tracing
    telemetry byte-for-byte).

    ``overlap`` (None reads ``HSTD_SERVE_OVERLAP``, default on) makes
    the decode loop DISPATCH-AHEAD (ISSUE 12): iteration N is
    dispatched before iteration N−1's tokens are fetched, and all the
    host work of the loop — committing N−1's tokens, phase stamps,
    admission, bucket pick, block math, prefill staging — runs
    concurrently with N's device compute; ``jax.device_get`` is
    deferred by exactly one iteration. The token feed for dispatch N
    is N−1's un-fetched DEVICE output (merged with host-known tokens
    for fresh-from-prefill slots by one warmed fixed-shape select —
    the decode step itself compiles zero new variants per bucket).
    Host decisions that depend on N−1's token values are re-derived
    one step late without changing emitted tokens: a budget finish is
    predicted from counts and excluded from dispatch N up front; an
    EOS finish is discovered at commit, and the wasted in-flight token
    is discarded (its stale K/V write is ordered before any
    reallocation of the released blocks by the pool-chain data
    dependency, so it can never clobber a later owner). Preemption /
    KV-pressure DRAINS the pipeline first (``overlap_flushes``
    latches every drain), so the recompute path always runs on
    committed state. A speculative engine commits each window before
    the next dispatch (acceptance counts are data-dependent) and
    overlaps the next iteration's admission/prefill/telemetry
    instead. ``overlap='off'`` restores the serial loop byte-for-byte
    in telemetry.

    ``mesh`` (ISSUE 13) makes the engine TENSOR-PARALLEL — one engine
    serving a model bigger than a chip. Pass a ``jax.sharding.Mesh``
    with a ``tensor`` axis, an int degree (a ``dp=1 × tp`` mesh over
    the first ``tp`` devices is built via
    ``parallel.mesh.tensor_parallel_mesh``), or None to read
    ``HSTD_SERVE_TP`` (default 1 = single-device). Params are placed
    with ``parallel.sharding.param_shardings`` (Megatron layout) and
    every per-layer KV pool — int8 scale pools included — shards its
    HEADS axis over ``tensor`` (``[num_blocks, block_size, H, D]``
    shards on H cleanly; ``num_kv_heads % tp == 0`` is required and
    rejected loudly otherwise, GQA included). Block tables, context
    lens and token feeds stay replicated, so the host-side scheduler,
    BlockManager, prefix cache, dispatch-ahead pipeline and timeline
    stamps are untouched — the TP engine emits token-identical output
    to the single-device engine. The KV byte budget re-denominates PER
    DEVICE: ``BlockManager.token_bytes`` becomes each shard's bytes
    per resident token (``1/tp`` of the model's), so
    ``kv_pool_bytes`` — a per-device figure — buys a TP=2 engine ~2x
    the blocks, and through the scheduler's block-denominated
    admission math, ~2x the concurrently-resident requests on the
    same per-chip memory. Compile expectations are unchanged: one
    step compile per bucket per engine (a TP plan is its own static
    key; sharding mints no extra variants within it).
    ``kernel='pallas'`` does not compose with ``mesh`` (the fused
    kernel would need a shard_map port) and is rejected loudly.

    ``swap`` (ISSUE 17, None reads ``HSTD_SERVE_SWAP``, default
    ``off``) turns on the host-RAM KV spill tier. Preemption victims
    are EXTRACTED to host (:func:`extract_blocks` — value pools and
    int8 scale pools atomically) instead of recomputed: on re-admit
    the blocks scatter back (:func:`insert_blocks`) and the request
    resumes DECODE with its output intact — no re-prefill, token
    emission bitwise what the uninterrupted run produces (the sampled
    fold indices are a pure function of output length, which swap
    never rewinds). ``auto`` picks swap vs recompute per victim by
    comparing bytes moved (2 × blocks × host block bytes) against the
    weight traffic re-prefill would stream (param bytes × prefill
    dispatches); ``always``/``never`` force the choice; ``never``
    still keeps the tier for PREFIX DEMOTION — zero-ref cached blocks
    write back to host before true eviction and revive on match, so
    the effective prefix cache is RAM-sized. ``swap_bytes`` (None
    reads ``HSTD_SERVE_SWAP_BYTES``) caps demoted payloads + swap
    reservations together; a victim that cannot reserve falls back to
    recompute. Extraction/insertion are per-block jitted
    gather/scatters over TRACED indices — zero new step variants, and
    both directions are precompiled at :meth:`warmup`. ``off`` keeps
    the engine (and its telemetry) byte-identical to the pre-tier
    build."""

    #: consecutive iterations a smaller bucket must suffice before the
    #: engine shrinks to it — bounds bucket churn when the max resident
    #: context oscillates around a bucket boundary
    SHRINK_PATIENCE = 4

    def __init__(self, model, params, *, num_slots: int = 8,
                 block_size: int = 16, num_blocks: int = 129,
                 prefill_chunk: int = 16,
                 max_model_len: Optional[int] = None,
                 gather_buckets: Union[str, Sequence[int], None] = None,
                 prefill_batch: int = 4,
                 speculate_k: Optional[int] = None,
                 draft=None,
                 prefix_cache: Union[str, bool, None] = None,
                 kernel: Union[str, None] = None,
                 kv_cache_dtype: Union[str, None] = None,
                 kv_pool_bytes: Optional[int] = None,
                 timeline: Union[str, bool, None] = None,
                 overlap: Union[str, bool, None] = None,
                 mesh=None,
                 swap: Union[str, None] = None,
                 swap_bytes: Union[str, int, None] = None,
                 policy: Union[str, None] = None,
                 aging_s: Union[str, float, None] = None):
        cfg = model.config
        if getattr(cfg, "num_experts", 0):
            raise ValueError(
                "ServeEngine does not support capacity-slot MoE models "
                "(models/moe.py::topk_dispatch): expert capacity depends "
                "on the apply's sequence length, so chunked prefill could "
                "drop token->expert assignments the one-shot path never "
                "drops. Dropless routed experts (models/moe.py::"
                "dropless_experts), whose routing depends on the token "
                "alone, are served")
        if getattr(cfg, "pipeline_stages", 0):
            raise ValueError("ServeEngine needs the dense stack "
                             "(pipeline_stages=0)")
        kernel = parse_kernel(kernel)
        # tensor-parallel mesh resolution (ISSUE 13): an explicit Mesh,
        # an int degree, or the HSTD_SERVE_TP env default
        from jax.sharding import Mesh as _Mesh

        if isinstance(mesh, _Mesh):
            self.mesh = mesh
            self.tp = int(mesh.shape.get("tensor", 1))
            if self.tp < 2:
                # a mesh without a >1 tensor axis is the single-device
                # engine with extra steps — treat it as one
                self.mesh = None
                self.tp = 1
        else:
            self.tp = parse_tp(mesh)
            if self.tp > 1:
                from huggingface_sagemaker_tensorflow_distributed_tpu.parallel.mesh import (
                    tensor_parallel_mesh,
                )

                self.mesh = tensor_parallel_mesh(self.tp)
            else:
                self.mesh = None
        self.kv_cache_dtype = parse_kv_dtype(
            kv_cache_dtype, getattr(cfg, "kv_cache_dtype", "fp"))
        if self.kv_cache_dtype != getattr(cfg, "kv_cache_dtype", "fp"):
            # the knob overrides the model's own cache storage: rebuild
            # the serving module around the adjusted config (params are
            # untouched — KV quantization is activation-side)
            if not hasattr(cfg, "kv_cache_dtype"):
                raise ValueError(
                    f"kv_cache_dtype={self.kv_cache_dtype!r} requested "
                    f"but {type(model).__name__} has no int8 KV cache "
                    "protocol")
            import dataclasses
            cfg = dataclasses.replace(cfg,
                                      kv_cache_dtype=self.kv_cache_dtype)
            model = type(model)(cfg)
        if self.mesh is not None:
            # place the params once, Megatron layout: qkv/FFN-in
            # column-parallel, attn-out/FFN-out row-parallel — the
            # committed shardings are what drive every jitted step's
            # SPMD partitioning (jit derives in-shardings from them)
            from huggingface_sagemaker_tensorflow_distributed_tpu.parallel.sharding import (
                param_shardings,
            )

            params = jax.device_put(params,
                                    param_shardings(params, self.mesh))
        self.model, self.params = model, params
        self.eos_token_id = int(cfg.eos_token_id)
        self.pad_token_id = min(int(cfg.pad_token_id), cfg.vocab_size - 1)
        if max_model_len is None:
            max_model_len = (cfg.max_position_embeddings
                             // block_size) * block_size
        self.max_model_len = int(max_model_len)
        max_pos = getattr(cfg, "max_position_embeddings", None)
        if max_pos is not None and self.max_model_len > max_pos:
            raise ValueError(
                f"max_model_len {self.max_model_len} exceeds the "
                f"model's max_position_embeddings {max_pos}")
        self.num_slots = int(num_slots)
        if speculate_k is None:
            speculate_k = int(os.environ.get(ENV_SPECULATE_K, "0") or 0)
        self.speculate_k = int(speculate_k)
        if self.speculate_k < 0:
            raise ValueError(f"speculate_k must be >= 0, "
                             f"got {self.speculate_k}")
        self.prefix_cache = parse_prefix_cache(prefix_cache)
        self.timeline = parse_timeline(timeline)
        self.overlap = parse_overlap(overlap)
        plan, pool_shapes = build_cache_plan(model, params,
                                             self.max_model_len,
                                             mesh=self.mesh)
        self._plan = plan
        # a latent-attention model (one `cached_latent` pool a layer):
        # which form each dispatch attends by is the model's own rule on
        # the dispatch's shape, named in the step spans' arguments
        self._latent = any(k[0] == "latent" for k in plan.kinds)
        # a model with recurrent (linear-attention) layers: per-slot state
        # pools beside the block pools (``CachePlan``'s ``state`` kind)
        self._stateful = bool(plan.state_shapes)
        self._routes = _routes(model)
        # what a model with a residual path of its own says of itself
        # (``residual_streams``, ``gate``): written beside ``latent_path``
        # on the step spans, in stats() and on the report; {} otherwise
        self._residual_kw = (dict(model.residual_kw())
                             if hasattr(model, "residual_kw") else {})
        # (token, expert) pairs one real token makes over the model
        self._moe_fanout = (int(cfg.num_experts_per_tok)
                            * int(cfg.num_moe_layers)) if self._routes else 0
        # which way a decode step attends: forced by ``kernel``, else
        # chosen from the platform, the plan's kinds, the mesh and the
        # pools' head size and storage; ``self.kernel`` names the result
        path = resolve_decode_path(
            kernel, platform=jax.default_backend(),
            pool_kinds=[k[0] for k in plan.kinds if k[0] in _POOLED],
            mesh=self.mesh is not None,
            head_dim=max(d for _h, d, _dt in pool_shapes),
            kv_dtype=self.kv_cache_dtype)
        self.kernel = "pallas" if path == "paged_kernel" else "xla"
        # a speculative window attends a gathered cache (``_spec_fn``)
        # whatever the plain step would have done
        self.decode_path = "gather" if self.speculate_k else path
        # how a prefill dispatch writes its chunks back: the rule the
        # traced program follows, on the same two numbers
        self.write_path = prefill_write_path(prefill_chunk, block_size)
        if (self._latent or self._routes) and self.speculate_k:
            raise ValueError(
                "speculative decoding is not wired for latent-"
                "attention or routed-expert models")
        if self._stateful and self.speculate_k:
            raise ValueError(
                "speculate_k: speculative decoding is not wired for a "
                "model with recurrent state: a rejected draft token has "
                "already advanced the state, and the rewind rolls back "
                "block tables only (ROADMAP R3: verify with state "
                "roll-back)")
        # bytes one resident token costs across every pool (int8 KV +
        # its fp32 scale plane included) — the figure that sizes a
        # byte-budgeted pool and denominates kv_bytes_read telemetry.
        # Under a tensor-parallel mesh this re-denominates PER DEVICE
        # (each shard holds H/tp heads of every pool — exact, the plan
        # already validated divisibility): kv_pool_bytes is a per-chip
        # budget, so a TP=2 engine on the same per-chip figure holds
        # ~2x the blocks and admits ~2x the concurrent requests — the
        # capacity win sharding buys
        token_bytes = sum(h * d * np.dtype(dtype).itemsize
                          for h, d, dtype in pool_shapes) // self.tp
        if kv_pool_bytes is not None:
            # size the pool by a KV MEMORY budget instead of a block
            # count: int8 pools (~half the bytes/token) get ~2x the
            # blocks — and through the scheduler's block-denominated
            # admission math, ~2x the resident requests — for the same
            # budget. The budget covers the TARGET pools; a speculative
            # draft's pools ride on top (its layer share).
            block_bytes = block_size * max(token_bytes, 1)
            num_blocks = max(2, 1 + int(kv_pool_bytes) // block_bytes)
        # a prefix hit hands a request K/V blocks and no state, which
        # would be silently wrong: for a plan with a ``state`` kind the
        # index matches nothing and registers nothing (requests are still
        # counted, at zero cached tokens)
        self.blocks = BlockManager(num_blocks, block_size,
                                   token_bytes=token_bytes,
                                   prefix_matching=not self._stateful)
        self.sched = Scheduler(num_slots, self.blocks, prefill_chunk,
                               self.max_model_len,
                               decode_lookahead=self.speculate_k + 1,
                               prefix_cache=self.prefix_cache,
                               policy=policy, aging_s=aging_s)
        # admission policy (ISSUE 20): parsed once by the scheduler;
        # "fifo" keeps every event stream byte-identical to the
        # pre-policy engine (all policy riders gate on != "fifo")
        self.policy = self.sched.policy
        self.max_blocks_per_seq = self.max_model_len // block_size
        if gather_buckets is None:
            gather_buckets = os.environ.get(ENV_GATHER_BUCKETS)
        self.gather_buckets = parse_gather_buckets(
            gather_buckets, self.max_model_len, block_size)
        if self.speculate_k:
            if self.speculate_k + 1 > self.max_model_len:
                raise ValueError(
                    f"speculate_k {self.speculate_k} verify window does "
                    f"not fit max_model_len {self.max_model_len}")
            # buckets too narrow for even an empty-context window can
            # never be selected — drop them so warmup compiles only
            # dispatchable variants (full width always remains)
            self.gather_buckets = [b for b in self.gather_buckets
                                   if b >= self.speculate_k + 1]
        self.prefill_batch = max(1, min(int(prefill_batch), self.num_slots))
        # the buckets a prefill dispatch can run at: a chunk must fit
        # (max_model_len, a multiple of the chunk, always does)
        self.prefill_buckets = [b for b in self.gather_buckets
                                if b >= self.sched.prefill_chunk]

        # place every pool heads-sharded over the mesh: the committed
        # shardings ARE the jitted steps' pool in-shardings, and
        # _constrain_pools pins the outputs to the same, so the
        # pools-chain stays on the mesh end to end. Sharded pools are
        # materialized from HOST zeros — device_put splits a numpy
        # array into per-device shards directly, whereas a jnp.zeros
        # would first allocate the FULL pool on one device, which is
        # exactly the footprint a bigger-than-a-chip model cannot fit
        self._pools = self._init_pools(num_blocks, block_size,
                                       pool_shapes, plan)
        # one state pool a recurrent leaf, row s slot s's: sized from
        # ``num_slots`` alone, no option sets it
        self._states = [jnp.zeros((self.num_slots,) + shape, dtype)
                        for shape, dtype in plan.state_shapes]
        self.state_bytes_per_slot = sum(
            int(np.prod(shape)) * np.dtype(dtype).itemsize
            for shape, dtype in plan.state_shapes)
        self.state_pool_bytes = self.num_slots * self.state_bytes_per_slot
        # speculative mode: the draft model's paged pools ride the SAME
        # block tables/allocator as the target's — one allocation
        # domain, two KV address spaces (per-block bytes grow by the
        # draft's layer share; the draft's context is the target's)
        self.draft_model = self.draft_params = None
        if self.speculate_k:
            if isinstance(draft, tuple):
                self.draft_model, self.draft_params = draft
            else:
                layers = draft
                if layers is None:
                    layers = int(os.environ.get(ENV_DRAFT_LAYERS, "0")
                                 or 0) or max(1, cfg.num_layers // 4)
                self.draft_model, self.draft_params = self_draft(
                    model, params, int(layers))
            if self.draft_model.config.vocab_size != cfg.vocab_size:
                raise ValueError(
                    "draft and target must share a vocabulary (got "
                    f"{self.draft_model.config.vocab_size} vs "
                    f"{cfg.vocab_size})")
            if self.mesh is not None:
                # the draft inherits the target's parallelism: its
                # params (a layer subset or a second checkpoint) place
                # by the same Megatron rules, its pools shard on the
                # same heads axis over the same mesh
                from huggingface_sagemaker_tensorflow_distributed_tpu.parallel.sharding import (
                    param_shardings,
                )

                self.draft_params = jax.device_put(
                    self.draft_params,
                    param_shardings(self.draft_params, self.mesh))
            d_plan, d_pool_shapes = build_cache_plan(
                self.draft_model, self.draft_params, self.max_model_len,
                mesh=self.mesh)
            self._d_plan = d_plan
            self._d_pools = self._init_pools(num_blocks, block_size,
                                             d_pool_shapes, d_plan)
        # the jitted step functions are MODULE-level and keyed on
        # (model, plan, width, sampled) static args: a second engine
        # over the same model/geometry — the bench's measured pass, a
        # restarted server — reuses the compiled executables instead of
        # retracing
        donate = jax.default_backend() != "cpu"
        self._donate = donate
        # multi-replica serving (ISSUE 14): the router sets this to the
        # replica index when the engine is one of N; every per-request
        # lifecycle event + the SLO report then carry `replica`, which
        # is what `obsctl slo` groups tail attribution by. None (the
        # default, and the single-replica router's choice) adds NOTHING
        # to the telemetry stream — the byte-identity contract.
        self.replica: Optional[int] = None
        self._decode_fn = (_paged_decode_step_jit(donate)
                           if self.decode_path == "paged_kernel"
                           else _decode_step_jit(donate))
        self._prefill_fn = _prefill_chunk_jit(donate)
        self._spec_fn = _spec_step_jit(donate)
        self._copy_fn = _copy_block_jit(donate)
        self.finished: dict[int, Request] = {}
        self._keys: dict[int, np.ndarray] = {}   # rid -> base PRNG key
        self.decode_steps = 0
        self.prefill_chunks = 0
        self.prefill_dispatches = 0
        self.prefill_dispatches_by_form = (
            dict.fromkeys(model.EXPANDED_FORMS, 0) if self._latent else None)
        self.prefill_keys_needed = 0
        self.prefill_keys_attended = 0
        self.tokens_generated = 0
        self.decode_tokens = 0
        self.decode_time_s = 0.0
        self.iterations = 0
        self.peak_waiting = 0
        self.bucket_switches = 0
        self.draft_proposed = 0
        self.draft_accepted = 0
        self.kv_bytes_read = 0      # pool bytes decode dispatches read
        # routed experts (a model with dropless experts, under a
        # telemetry sink only: the counts are device arrays that ride out
        # of the steps and are fetched once a later fetch has shown them
        # computed): (token, expert) pairs of the real tokens dispatched,
        # and those that landed on the experts held here
        self.moe_pairs = 0
        self.moe_pairs_held = 0
        # (pairs, device counts, is decode[, device defect]) a dispatch
        self._moe_flight: list = []
        self._moe_resolved = 0        # entries taken off its head so far
        self._moe_landed = 0          # entries of the run a fetch has passed
        self.spec_windows = 0       # active (slot, iteration) pairs
        self.peak_resident = 0      # max concurrently-occupied slots
        # open-loop SLO accounting (ISSUE 16): attainment counters over
        # finished requests that carried targets, plus the per-group
        # split and the peak count of arrival-stamped requests seen
        # waiting at any ledger instant. The _has_* flags gate every new
        # report/ledger field so a closed-loop run's stream stays
        # byte-identical to the pre-open-loop engine's.
        self._slo_total = 0
        self._slo_met = 0
        self._group_slo: dict[str, list] = {}   # group -> [met, total]
        self._arrival_backlog_peak = 0
        self._has_arrivals = False
        self._has_slo = False
        # admission-policy accounting (ISSUE 20): deadline verdicts
        # over finished requests that carried one, and per-priority-
        # class SLO attainment. _has_priorities flips on the first
        # nonzero-priority submit; all riders stay absent otherwise.
        self._deadline_total = 0
        self._deadline_miss = 0
        self._priority_slo: dict[int, list] = {}  # class -> [met, total]
        self._has_priorities = False
        self._bucket = self.gather_buckets[0]
        self._shrink_streak = 0
        self._warmed_modes: set = set()
        # dispatch-ahead pipeline state (ISSUE 12): the one in-flight
        # decode dispatch (plain) or speculative window, and how many
        # times the pipeline was force-drained (preemption/KV pressure
        # must act on committed state)
        self._pending: Optional[_PendingDecode] = None
        self._pending_spec: Optional[_PendingSpec] = None
        self.overlap_flushes = 0
        # lifecycle tracing (ISSUE 10): per-iteration dispatch-time
        # accumulators the iteration_ledger event reads (reset each
        # step; populated only with `timeline` on)
        self._iter_prefill_s = 0.0
        self._iter_decode_s = 0.0
        self._iter_decode_slots = 0
        # recurrent state (a model that has it): rows whose state this
        # iteration's dispatches read and wrote, the tokens its decode
        # dispatch attended (each slot's context and the token written),
        # and the real prompt tokens of its prefill dispatches
        self._iter_state_slots = 0
        self._iter_kv_resident = 0
        self._iter_prefill_tokens = 0
        # the iteration's account of its own wall time (always on:
        # stamps and float adds): the four parts of THIS iteration, the
        # last stamp, when the previous iteration returned, and the
        # run's sums of the parts, of dur_s and of gap_s
        self._iter_parts = [0.0, 0.0, 0.0, 0.0]
        self._t_lap = 0.0
        self._t_step_end: Optional[float] = None
        self._host_totals = [0.0, 0.0, 0.0, 0.0, 0.0, 0.0]
        # host-RAM KV spill tier (ISSUE 17). `off` leaves every hook
        # uninstalled — scheduler, BlockManager and telemetry behave
        # byte-identically to the pre-tier engine. Otherwise the
        # scheduler's preemption path gets the swap hook and (with the
        # prefix cache on) the BlockManager gets the spill/demotion
        # hook, both closing over the live pools.
        self.swap = parse_swap(swap)
        if self._stateful and self.swap != "off":
            raise ValueError(
                f"swap={self.swap!r}: the host spill tier is not wired for "
                "a model with recurrent state: a swapped BlockSet carries "
                "blocks and no state (ROADMAP R3: state snapshots). "
                "Preemption recomputes: a re-admitted request starts at 0")
        self.swap_bytes = parse_swap_bytes(swap_bytes)
        self.swap_ins = 0
        self.swap_outs = 0
        self.swap_bytes_moved = 0
        self.restore_s = 0.0
        self.recompute_tokens_avoided = 0
        # cross-engine transport (ISSUE 18): counters stay zero —
        # and every rider stays absent — unless migrate_request runs,
        # the byte-identity contract for single-engine traffic.
        # _migrated_in maps an adopted resident's rid to its source
        # replica index until the restore applies, which is how
        # _apply_restores tells a migration arrival (migration
        # accounting, `migrate` event) from a swap-tier re-admission
        # (host-budget release, `swap_in` event).
        self.migrations_in = 0
        self.migrations_out = 0
        self.migration_bytes = 0
        self.migration_restore_s = 0.0
        self._migrated_in: dict = {}
        # fleet tracing (ISSUE 19): per-hop transport seconds observed
        # at this engine's restore applies (migrate-out stamp →
        # scatter-complete), the sample list behind the router's
        # transport_hop_s_p99 rider. _migrate_hold marks rids whose
        # NEXT admission closes a migration hold — the stamp tags that
        # preempted segment `via: "migrate"` so the stitcher can split
        # cross-engine admission wait out of same-engine preemption.
        self.transport_hop_s: list = []
        self._migrate_hold: set = set()
        # role-designated prefill replica (ISSUE 18): the Router flips
        # this on disaggregated fleets; _step then suppresses the
        # decode phase entirely and finished prefills park in DECODE
        # state until the router migrates them to a decode replica
        self.prefill_only = False
        if self.swap != "off":
            # host bytes one block costs across every pool, UNSHARDED
            # (device_get assembles the full logical block regardless
            # of tp), draft pools included — the figure behind both
            # the budget charge and the auto estimate's bytes-moved
            # side. The recompute side streams the params once per
            # prefill dispatch, so the crossover is
            #   2 * blocks * host_block_bytes
            #     vs param_bytes * ceil(context / prefill_chunk)
            self._host_block_bytes = block_size * sum(
                h * d * np.dtype(dtype).itemsize
                for h, d, dtype in pool_shapes)
            if self.speculate_k:
                self._host_block_bytes += block_size * sum(
                    h * d * np.dtype(dtype).itemsize
                    for h, d, dtype in d_pool_shapes)
            self._param_bytes = sum(
                x.size * x.dtype.itemsize
                for x in jax.tree_util.tree_leaves(self.params))
            self.sched.swap_hook = self._swap_out
            if self.prefix_cache:
                self.blocks.set_spill(self._spill_block,
                                      host_budget=self.swap_bytes)

    @staticmethod
    def _init_pools(num_blocks: int, block_size: int, pool_shapes,
                    plan: CachePlan) -> list:
        """Zeroed KV pools, placed per the plan. Sharded pools go
        through ``jax.device_put(host_zeros, sharding)`` so each
        device only ever materializes its own ``1/tp`` shard — a
        ``jnp.zeros`` would transiently allocate the WHOLE pool on the
        default device first, OOMing init in precisely the
        bigger-than-a-chip regime TP serves."""
        dims = pool_dims(plan, pool_shapes, num_blocks, block_size)
        if not plan.kv_shardings:
            return [jnp.zeros(shape, dtype)
                    for shape, (_h, _d, dtype) in zip(dims, pool_shapes)]
        return [jax.device_put(np.zeros(shape, np.dtype(dtype)), s)
                for shape, (_h, _d, dtype), s in zip(
                    dims, pool_shapes, plan.kv_shardings)]

    # -- public API ----------------------------------------------------------

    def _replica_kw(self) -> dict:
        """``{"replica": i}`` when this engine is replica i of a router
        fleet, ``{}`` otherwise — the single spot that keeps a
        router-less (or replicas=1) engine's telemetry byte-identical
        to the pre-router stream."""
        return {} if self.replica is None else {"replica": self.replica}

    def _trace_kw(self, req: Request) -> dict:
        """``{"trace_id": ..., "hop": ...}`` when the request carries a
        router-minted trace context (ISSUE 19), ``{}`` otherwise — the
        absent-when-default twin of :meth:`_replica_kw`: untraced runs
        emit byte-identical events to the pre-tracing stream."""
        if not req.trace_id:
            return {}
        return {"trace_id": req.trace_id, "hop": req.hop}

    def take_waiting(self) -> list[Request]:
        """Drain hook (ISSUE 14): remove and return every WAITING
        request (the scheduler's :meth:`~.scheduler.Scheduler.
        take_waiting`), dropping their engine-side sampled-key entries
        — the adopting replica re-derives them (:meth:`adopt`), and a
        stale entry here would leak per-request state past the
        request's departure. Resident requests finish on this engine."""
        moved = self.sched.take_waiting()
        for req in moved:
            self._keys.pop(req.rid, None)
        return moved

    def adopt(self, req: Request) -> None:
        """Requeue hook (ISSUE 14): enqueue an EXISTING request — a
        sibling replica's drain victim — keeping its identity, folded
        prompt, and submit stamp. The sampled PRNG key re-derives from
        the request's own seed (token n's key is ``fold_in(PRNGKey(
        seed), n)``, a pure function of (seed, n)), so a moved sampled
        stream is bitwise what it would have been anywhere else —
        placement can never change tokens."""
        self.sched.adopt(req)
        if req.sampled:
            self._keys[req.rid] = np.asarray(jax.random.PRNGKey(req.seed),
                                             np.uint32)

    def adopt_resident(self, req: Request,
                       from_replica: Optional[int] = None) -> None:
        """Migration hook (ISSUE 18): enqueue a sibling engine's LIVE
        resident at the queue front (:meth:`~.scheduler.Scheduler.
        adopt_resident`). A hot migrant carries its extracted block
        set as ``swap_set`` — registering its rid here routes the
        eventual restore through migration accounting instead of the
        swap tier's; a cold (mid-prefill) migrant just re-prefills.
        The sampled key re-derives exactly as :meth:`adopt` — token
        ``n``'s key is a pure function of (seed, n), so migration can
        never change tokens."""
        self.sched.adopt_resident(req)
        if req.swap_set is not None:
            self._migrated_in[req.rid] = from_replica
        else:
            self.migrations_in += 1
        if req.trace_id:
            self._migrate_hold.add(req.rid)
        if req.sampled:
            self._keys[req.rid] = np.asarray(jax.random.PRNGKey(req.seed),
                                             np.uint32)

    def load_gauges(self) -> dict:
        """Live host-side load gauges (ISSUE 14): the placement-policy
        inputs — waiting depth, occupied slots, and KV pool pressure —
        read straight off the scheduler/BlockManager so a router never
        parses its own telemetry stream to route. These are the same
        figures the per-iteration ``serve/waiting_depth`` /
        ``serve/running_slots`` series and the ledger's
        ``kv_used_frac`` carry."""
        return {
            "waiting_depth": len(self.sched.waiting),
            "running": sum(1 for s in self.sched.slots if not s.free),
            "kv_used_frac": self.blocks.utilization(),
        }

    def has_work(self) -> bool:
        """True while anything is queued, resident, or in flight in
        the dispatch-ahead pipeline — the loop condition :meth:`run`
        (and a router driving several engines) spins on."""
        return (self.sched.has_work() or self._pending is not None
                or self._pending_spec is not None)

    def submit(self, prompt, max_new_tokens: int, *,
               temperature: float = 0.0, top_k: int = 0,
               top_p: float = 0.0, seed: int = 0,
               group: str = "", arrival_s: Optional[float] = None,
               slo=None, trace_id: str = "",
               deadline_s: Optional[float] = None,
               priority: int = 0) -> Request:
        """Queue one request. ``temperature == 0`` (default) is greedy;
        ``temperature > 0`` samples with the given truncation knobs,
        seeded per request — same knob semantics as
        ``models.generate.generate_causal``. ``group`` is an opaque
        tag (tenant, route) the request's ``request_timeline`` event
        carries so SLO attribution can aggregate per group.

        Open-loop contract (ISSUE 16): ``arrival_s`` is the request's
        arrival stamp in this process's ``perf_counter`` domain —
        distinct from the submit stamp taken here, so queue wait
        decomposes into pre-submit backlog (load-generator hold time)
        plus in-engine queue. ``slo`` is any object with ``ttft_s`` /
        ``tpot_s`` attributes (``serve.loadgen.SloSpec``; duck-typed
        to keep this module import-free of the load generator) naming
        per-axis deadline seconds; the finish event then carries the
        verdicts and :meth:`slo_summary` the attainment. Both are
        absent-when-default: a closed-loop submit adds nothing to the
        telemetry stream.

        Admission-policy contract (ISSUE 20): ``deadline_s`` is an
        end-to-end deadline measured from the request's origin
        (``arrival_s`` when threaded, else the submit stamp) and
        ``priority`` the admission class, smaller = more urgent.
        Under ``policy="slo"`` both order WHO admits WHEN — never
        WHAT; under fifo they still drive the finish-side
        ``deadline_miss`` verdict. Absent-when-default like every
        other rider: no deadline and priority 0 add nothing."""
        req = Request(prompt=np.asarray(prompt, np.int32),
                      max_new_tokens=int(max_new_tokens),
                      temperature=float(temperature), top_k=int(top_k),
                      top_p=float(top_p), seed=int(seed),
                      group=str(group),
                      arrival_s=(None if arrival_s is None
                                 else float(arrival_s)),
                      slo_ttft_s=(None if slo is None or slo.ttft_s is None
                                  else float(slo.ttft_s)),
                      slo_tpot_s=(None if slo is None or slo.tpot_s is None
                                  else float(slo.tpot_s)),
                      trace_id=str(trace_id),
                      deadline_s=(None if deadline_s is None
                                  else float(deadline_s)),
                      priority=int(priority))
        req.submit_t = time.perf_counter()
        self.sched.submit(req)
        if req.sampled:
            self._keys[req.rid] = np.asarray(jax.random.PRNGKey(req.seed),
                                             np.uint32)
        if req.arrival_s is not None:
            self._has_arrivals = True
        if req.has_slo:
            self._has_slo = True
        if req.priority:
            self._has_priorities = True
        if obs.has_sink():
            extra = {}
            if req.arrival_s is not None:
                extra["arrival_s"] = round(req.arrival_s, 6)
            if req.slo_ttft_s is not None:
                extra["slo_ttft_s"] = req.slo_ttft_s
            if req.slo_tpot_s is not None:
                extra["slo_tpot_s"] = req.slo_tpot_s
            if req.deadline_s is not None:
                extra["deadline_s"] = req.deadline_s
            if req.priority:
                extra["priority"] = req.priority
            obs.serve("submit", request=req.rid,
                      prompt_len=len(req.prompt),
                      max_new_tokens=req.max_new_tokens,
                      sampled=req.sampled, **self._replica_kw(),
                      **self._trace_kw(req), **extra)
        return req

    def output_ids(self, req: Request) -> np.ndarray:
        """Generated ids (preemption-folded tokens included)."""
        folded = req.prompt[req.orig_prompt_len:]
        return np.concatenate(
            [folded, np.asarray(req.output, np.int32)]).astype(np.int32)

    @property
    def speculative(self) -> bool:
        return self.speculate_k > 0

    def warmup(self, sampled: bool = False) -> None:
        """Compile every prefill program :meth:`_prefill_batch` can
        dispatch (the batched ``[prefill_batch, C]`` shape at every
        bucket a chunk fits, the lone-request ``[1, C]`` shape at the
        first of them only) and EVERY bucket's decode (or speculative
        draft/verify) step on null work so the serving loop itself
        never traces: the compile-tracker event count stays flat
        across steady state (``tests/test_serve_gates.py`` holds it at 0
        per mechanism). With ``sampled=True`` the per-slot-sampling variants
        of every step are ALSO precompiled — without it they compile
        lazily on the first sampled batch (one mid-serve stall per
        bucket), which latency-sensitive sampled traffic should not
        pay. Idempotent per mode; ``warmup(sampled=True)`` after a
        plain warmup compiles only the sampled variants."""
        modes = [False] + ([True] if sampled else [])
        modes = [m for m in modes if m not in self._warmed_modes]
        if not modes:
            return
        # life-cycle spans: kept even though no telemetry directory is
        # configured yet (a benchmark switches telemetry on after
        # warm-up), one child per program, each ending when the device
        # has run it
        with self._mesh_ctx(), obs.lifecycle_span("serve/warmup"):
            C = self.sched.prefill_chunk
            nb = self.max_blocks_per_seq
            S = self.num_slots

            def publish(name, fn, args, static, **key):
                # the program map (obs/programs.py): the callable and the
                # SHAPES it is about to run with, for a later resolve
                obs.programs.register(
                    name, fn, args, static=static, key=key,
                    root=type(self.model).__name__,
                    context=functools.partial(_mesh_context, self.mesh))
            sf = np.zeros((S,), np.float32)
            si = np.zeros((S,), np.int32)
            for mode in modes:
                # the prefill programs _prefill_batch can dispatch: the
                # batched [prefill_batch, C] shape at every bucket a
                # chunk fits, the lone-request [1, C] shape at the
                # first of them only (the draft's prefill rides the
                # target's greedy variant only — drafts never sample
                # at prefill)
                for G in sorted({1, self.prefill_batch}):
                    zf = np.zeros((G,), np.float32)
                    zi = np.zeros((G,), np.int32)
                    null = (np.zeros((G, C), np.int32),
                            np.zeros((G, nb), np.int32),
                            zi, np.full((G,), -1, np.int32), zf, zi, zf,
                            np.zeros((G, 2), np.uint32), zi)
                    null_rows = np.full((G,), S, np.int32)
                    with obs.lifecycle_span(f"serve/warmup/prefill_g{G}"):
                        for width in (self.prefill_buckets
                                      if G == self.prefill_batch
                                      else self.prefill_buckets[:1]):
                            with obs.lifecycle_span(
                                    f"serve/warmup/prefill_g{G}/w{width}"):
                                args = (self.model, self.params, self._pools,
                                        *null, self._plan, mode, width,
                                        *self._state_args(null_rows))
                                publish("prefill_chunk", self._prefill_fn,
                                        args, (0, 12, 13, 14), rows=G,
                                        width=width, sampled=mode)
                                tok, _ = self._step_out(
                                    self._prefill_fn(*args))
                                if self.speculative and not mode:
                                    tok, self._d_pools, *_ = self._prefill_fn(
                                        self.draft_model, self.draft_params,
                                        self._d_pools, *null, self._d_plan,
                                        False, width)
                                jax.block_until_ready(tok)
                for bucket in self.gather_buckets:
                    with obs.lifecycle_span(
                            f"serve/warmup/decode_b{bucket}"):
                        if self.speculative:
                            args = (self.model, self.params, self.draft_model,
                                    self.draft_params, self._pools,
                                    self._d_pools, si,
                                    np.zeros((S, nb), np.int32), si,
                                    np.zeros((S,), bool), sf, si, sf,
                                    np.zeros((S, 2), np.uint32), si,
                                    self._plan, self._d_plan, bucket,
                                    self.speculate_k, mode)
                            publish("spec_decode_step", self._spec_fn, args,
                                    (0, 2, 15, 16, 17, 18, 19), bucket=bucket,
                                    slots=S, sampled=mode)
                            (_, _, tok, self._pools,
                             self._d_pools) = self._spec_fn(*args)
                        else:
                            def decode_args(tokens):
                                return (self.model, self.params, self._pools,
                                        tokens, np.zeros((S, nb), np.int32),
                                        si, np.zeros((S,), bool), sf, si, sf,
                                        np.zeros((S, 2), np.uint32), si,
                                        self._plan, bucket, mode,
                                        *self._state_args())

                            publish(self._decode_fn.__name__.lstrip("_"),
                                    self._decode_fn, decode_args(si),
                                    (0, 12, 13, 14), bucket=bucket, slots=S,
                                    sampled=mode)
                            tok, _ = self._step_out(
                                self._decode_fn(*decode_args(si)))
                            if self.overlap:
                                # the dispatch-ahead loop feeds the
                                # previous step's device-resident tokens
                                # straight back in. Under a mesh a
                                # committed array's sharding is part of
                                # the executable's key, so that feed is
                                # a second compile (found on four chips:
                                # two 5 s compiles mid-serve); on one
                                # device it is a cache hit
                                tok, _ = self._step_out(
                                    self._decode_fn(*decode_args(tok)))
                        jax.block_until_ready(tok)
            if (self.overlap and not self.speculative
                    and not self._warmed_modes):
                # precompile the dispatch-ahead token-feed select (the
                # host-known-token merge over the previous dispatch's
                # un-fetched device output) — one fixed-shape [S]
                # executable, so the pipelined loop mints zero compiled
                # variants beyond the serial loop's own set
                tok = jnp.where(np.zeros((S,), bool), tok,
                                np.zeros((S,), np.int32))
            if self.prefix_cache and not self._warmed_modes:
                # precompile the COW block copy (null-block self-copy:
                # a no-op) so a cache hit that must privatize never
                # traces mid-serve — the "hit path adds zero new
                # compiled variants" contract
                self._pools = self._copy_fn(self._pools,
                                            np.int32(0), np.int32(0))
                if self.speculative:
                    self._d_pools = self._copy_fn(self._d_pools,
                                                  np.int32(0), np.int32(0))
            if self.swap != "off" and not self._warmed_modes:
                # precompile BOTH spill-tier directions (a null-block
                # self-round-trip: extract reads block 0, insert puts
                # the same zeros back) so a mid-serve swap-out, prefix
                # demotion, or restore never traces — the "zero new
                # step variants" contract of ISSUE 17
                d = self._d_pools if self.speculative else None
                bset = extract_blocks(self._pools, [0], d_pools=d)
                self._pools, d = insert_blocks(
                    self._pools, bset, [0], d_pools=d,
                    donate=self._donate)
                if self.speculative:
                    self._d_pools = d
            jax.block_until_ready(tok)
        if not self._warmed_modes:
            # announce the starting bucket so every instrumented run
            # has a bucket baseline to diff switches against
            obs.serve("bucket_switch", gather_bucket=self._bucket,
                      prev_bucket=None, max_context=0)
        self._warmed_modes.update(modes)

    def run(self) -> dict[int, Request]:
        """Drive the loop until every submitted request finishes;
        returns {rid: Request}. Ends with one ``serve`` *report* event
        carrying the run's SLO summary (TTFT / end-to-end latency
        percentiles, gather-bucket accounting) so the cross-host report
        (`obs/report.py`) reads the serving story from a single line."""
        self.warmup()
        with obs.span("serve/run"):
            while self.has_work():
                self.step()
        obs.scalar("serve/kv_peak_utilization",
                   self.blocks.peak_used / max(self.blocks.num_blocks - 1, 1))
        summary = self.slo_summary()
        if summary:
            obs.serve("report", **summary)
        return self.finished

    def host_loop_totals(self) -> dict:
        """The run's sums of every iteration's account of its wall time
        (seconds): ``dur_s`` and its four disjoint parts, ``gap_s``
        between iterations, and ``iterations``. Kept with or without a
        telemetry sink; with one, they are the sums of the
        ``iteration_ledger`` lines' fields."""
        out = dict(zip(HOST_LOOP_FIELDS, self._host_totals))
        out["iterations"] = self.iterations
        return out

    def _lap(self, part: int) -> float:
        """Stamp the clock; the stretch since the last stamp was spent
        on ``part`` of this iteration. Returns the stamp."""
        now = time.perf_counter()
        self._iter_parts[part] += now - self._t_lap
        self._t_lap = now
        return now

    def slo_summary(self) -> dict:
        """TTFT / end-to-end latency percentiles + scheduler/gather
        gauges over every FINISHED request ({} until one finishes)."""
        reqs = list(self.finished.values())
        if not reqs:
            return {}
        ttfts = [r.ttft_s for r in reqs if r.ttft_s is not None]
        e2es = [r.finish_t - r.submit_t for r in reqs
                if r.finish_t is not None and r.submit_t is not None]
        out = {
            "requests": len(reqs),
            "sampled_requests": sum(1 for r in reqs if r.sampled),
            "tokens": self.tokens_generated,
            "iterations": self.iterations,
            "preemptions": self.sched.n_preemptions,
            "peak_waiting_depth": self.peak_waiting,
            "bucket_switches": self.bucket_switches,
            "gather_bucket": self._bucket,
            "prefill_dispatches": self.prefill_dispatches,
            "prefill_keys_needed": self.prefill_keys_needed,
            "prefill_keys_attended": self.prefill_keys_attended,
            "gather_read_waste_peak": round(
                self.blocks.peak_gather_waste, 4),
            "gather_read_waste_mean": round(
                self.blocks.gather_waste(), 4),
            "kv_peak_utilization": round(
                self.blocks.peak_used
                / max(self.blocks.num_blocks - 1, 1), 4),
        }
        if self.decode_time_s > 0:
            out["decode_tokens_per_sec"] = round(
                self.decode_tokens / self.decode_time_s, 1)
        out["kernel"] = self.kernel
        out["decode_path"] = self.decode_path
        out["write_path"] = self.write_path
        out["kv_dtype"] = self.kv_cache_dtype
        # latent cache / routed experts: absent for any other model
        if self._latent:
            out["latent_bytes_per_token"] = self.blocks.token_bytes
            out["prefill_dispatches_by_form"] = dict(
                self.prefill_dispatches_by_form)
        out.update(self._residual_kw)
        if self._routes:
            self._moe_resolve(everything=True)
            out["moe_pairs"] = self.moe_pairs
            out["moe_pairs_held"] = self.moe_pairs_held
        # recurrent state: absent for any other model
        if self._stateful:
            out["state_bytes_per_slot"] = self.state_bytes_per_slot
            out["state_pool_bytes"] = self.state_pool_bytes
            out["state_slots_peak"] = self.peak_resident
            out["state_step"] = self.model.state_step()
            out["state_chunk"] = self.model.state_chunk()
            out["kv_token_bytes"] = self.blocks.token_bytes
        # multi-replica serving (ISSUE 14): a router-owned replica's
        # report names itself so the merged cross-host report (and
        # `obsctl slo`'s per-replica grouping) can attribute it; absent
        # on router-less engines — the byte-identity contract
        out.update(self._replica_kw())
        # tensor-parallel serving (ISSUE 13): the degree + the pool's
        # per-device byte footprint (what `obsctl diff` watches as
        # serve_kv_pool_bytes_per_device — more bytes per device for
        # the same capacity is worse)
        out["tp"] = self.tp
        out["kv_pool_bytes_per_device"] = self.blocks.pool_bytes
        if self.overlap:
            # dispatch-ahead accounting (absent entirely with the
            # overlap off — that stream stays byte-identical to the
            # serial engine's)
            out["overlap"] = True
            out["overlap_flushes"] = self.overlap_flushes
        if self.decode_steps:
            out["kv_bytes_read_per_step"] = round(
                self.kv_bytes_read / self.decode_steps, 1)
        from huggingface_sagemaker_tensorflow_distributed_tpu.obs.report import (
            percentile,
        )

        if self.timeline:
            # lifecycle decomposition aggregates (ISSUE 10): queue-wait
            # percentiles and run-wide phase-time fractions over the
            # finished requests — the live decision inputs SLO-aware
            # admission needs, and the figures `obsctl diff` gates on
            # (absent entirely with the timeline off, keeping the
            # report event byte-identical to the pre-tracing stream)
            qs = sorted(r.phase_s["queue"] for r in reqs)
            out["queue_wait_p50_s"] = round(percentile(qs, 0.50), 6)
            out["queue_wait_p99_s"] = round(percentile(qs, 0.99), 6)
            tot = sum(e2es)
            if tot > 0:
                sums = {ph: sum(r.phase_s[ph] for r in reqs)
                        for ph in ("queue", "prefill", "decode",
                                   "preempted")}
                for ph, v in sums.items():
                    out[f"{ph}_time_frac"] = round(v / tot, 4)
                out["overhead_time_frac"] = round(
                    1.0 - sum(sums.values()) / tot, 4)

        if self.prefix_cache:
            cached = sum(r.prefix_cached_tokens for r in reqs)
            admitted = sum(r.prefix_prompt_tokens for r in reqs)
            out["prefix_cache"] = self.prefix_cache_state
            out["prefix_cached_tokens"] = cached
            out["cache_hit_rate"] = (round(cached / admitted, 4)
                                     if admitted else 0.0)
            out["blocks_shared_peak"] = self.blocks.peak_shared_blocks
            out["blocks_saved_peak"] = self.blocks.peak_blocks_saved
            out["cow_copies"] = self.blocks.cow_copies
            out["prefix_evictions"] = self.blocks.prefix_evictions
            out["shared_read_frac"] = round(
                self.blocks.shared_read_frac(), 4)
        out["peak_resident_requests"] = self.peak_resident

        # open-loop SLO attainment (ISSUE 16): the DistServe goodput
        # numerator — fraction of deadline-carrying finished requests
        # that met EVERY set target, plus the per-group (tenant) split
        # and the peak arrival-stamped backlog. Each key is gated on
        # its own feed having appeared, so closed-loop (and target-
        # less open-loop) reports stay byte-identical to before.
        if self._has_slo and self._slo_total:
            out["slo_attainment"] = round(
                self._slo_met / self._slo_total, 4)
            out["group_slo_attainment"] = {
                g: round(m / t, 4)
                for g, (m, t) in sorted(self._group_slo.items()) if t}
        if self._has_arrivals:
            out["arrival_backlog_peak"] = self._arrival_backlog_peak

        # admission policy (ISSUE 20): each rider gated on its own
        # feed so a fifo run (and a deadline-less / priority-less slo
        # run) reports byte-identically to the pre-policy engine
        if self.policy != "fifo":
            out["policy"] = self.policy
            out["aging_promotions"] = self.sched.aging_promotions
        if self._deadline_total:
            out["deadline_miss_frac"] = round(
                self._deadline_miss / self._deadline_total, 4)
        if self._has_priorities and self._slo_total:
            out["priority_slo_attainment"] = {
                str(p): round(m / t, 4)
                for p, (m, t) in sorted(self._priority_slo.items())
                if t}

        # host-RAM spill tier (ISSUE 17): swap traffic and prefix
        # demotion-tier accounting — absent entirely with the tier off,
        # keeping that report byte-identical to the pre-tier engine's
        if self.swap != "off":
            out["swap_policy"] = self.swap
            out["swap_outs"] = self.swap_outs
            out["swap_ins"] = self.swap_ins
            out["swap_bytes"] = self.swap_bytes_moved
            out["restore_s"] = round(self.restore_s, 6)
            out["recompute_tokens_avoided"] = self.recompute_tokens_avoided
            out["host_tier_hits"] = self.blocks.host_tier_hits
            out["host_tier_hit_rate"] = round(
                self.blocks.host_tier_hits
                / max(1, self.blocks.host_tier_lookups), 4)

        # cross-engine transport (ISSUE 18): absent entirely unless a
        # migration touched this engine — the byte-identity contract
        # for single-engine and migration-free fleet traffic
        if self.migrations_in or self.migrations_out:
            out["migrations_in"] = self.migrations_in
            out["migrations_out"] = self.migrations_out
            out["migration_bytes"] = self.migration_bytes
            out["migration_restore_s"] = round(
                self.migration_restore_s, 6)

        if self.speculative:
            out["speculate_k"] = self.speculate_k
            out["draft_proposed"] = self.draft_proposed
            out["draft_accepted"] = self.draft_accepted
            if self.draft_proposed:
                out["acceptance_rate"] = round(
                    self.draft_accepted / self.draft_proposed, 4)
            # the PER-REQUEST acceptance distribution: the aggregate
            # hides a single request speculating badly (a pathological
            # prompt for the draft) — p50/min name it
            rates = sorted(r.spec_accepted / r.spec_proposed
                           for r in reqs if r.spec_proposed)
            if rates:
                out["acceptance_rate_p50"] = round(
                    percentile(rates, 0.50), 4)
                out["acceptance_rate_min"] = round(rates[0], 4)
            out["verify_read_waste_peak"] = round(
                self.blocks.peak_verify_waste, 4)
            out["verify_read_waste_mean"] = round(
                self.blocks.verify_waste(), 4)

        for label, vals in (("ttft", ttfts), ("e2e", e2es)):
            if not vals:
                continue
            s = sorted(vals)
            out[f"{label}_p50_s"] = round(percentile(s, 0.50), 6)
            out[f"{label}_p95_s"] = round(percentile(s, 0.95), 6)
            out[f"{label}_p99_s"] = round(percentile(s, 0.99), 6)
        return out

    def stats(self) -> EngineStats:
        self._moe_resolve(everything=True)
        return EngineStats(
            latent_bytes_per_token=(self.blocks.token_bytes
                                    if self._latent else None),
            moe_pairs=self.moe_pairs,
            moe_pairs_held=self.moe_pairs_held,
            **self._residual_kw,
            prefill_dispatches_by_form=(
                dict(self.prefill_dispatches_by_form)
                if self._latent else None),
            decode_steps=self.decode_steps,
            prefill_chunks=self.prefill_chunks,
            prefill_dispatches=self.prefill_dispatches,
            prefill_keys_needed=self.prefill_keys_needed,
            prefill_keys_attended=self.prefill_keys_attended,
            tokens_generated=self.tokens_generated,
            decode_tokens=self.decode_tokens,
            decode_time_s=self.decode_time_s,
            preemptions=self.sched.n_preemptions,
            bucket_switches=self.bucket_switches,
            kv_peak_utilization=self.blocks.peak_used
            / max(self.blocks.num_blocks - 1, 1),
            kv_utilization=self.blocks.utilization(),
            gather_waste_peak=self.blocks.peak_gather_waste,
            gather_waste_mean=self.blocks.gather_waste(),
            draft_proposed=self.draft_proposed,
            draft_accepted=self.draft_accepted,
            acceptance_rate=(self.draft_accepted / self.draft_proposed
                             if self.draft_proposed else None),
            spec_windows=self.spec_windows,
            verify_waste_peak=self.blocks.peak_verify_waste,
            verify_waste_mean=self.blocks.verify_waste(),
            prefix_cache=self.prefix_cache_state,
            prefix_cached_tokens=sum(
                r.prefix_cached_tokens for r in self.finished.values()),
            cache_hit_rate=self._aggregate_hit_rate(),
            blocks_shared_peak=self.blocks.peak_shared_blocks,
            blocks_saved_peak=self.blocks.peak_blocks_saved,
            cow_copies=self.blocks.cow_copies,
            prefix_evictions=self.blocks.prefix_evictions,
            shared_read_frac=self.blocks.shared_read_frac(),
            peak_resident_requests=self.peak_resident,
            kernel=self.kernel,
            decode_path=self.decode_path,
            # an engine decodes one way for life
            decode_steps_by_path={
                p: self.decode_steps if p == self.decode_path else 0
                for p in ("paged_kernel", "gather")},
            write_path=self.write_path,
            kv_dtype=self.kv_cache_dtype,
            kv_bytes_read=self.kv_bytes_read,
            kv_token_bytes=self.blocks.token_bytes,
            overlap=self.overlap,
            overlap_flushes=self.overlap_flushes,
            tp=self.tp,
            kv_pool_bytes_per_device=self.blocks.pool_bytes,
            swap_policy=self.swap,
            swap_outs=self.swap_outs,
            swap_ins=self.swap_ins,
            swap_bytes=self.swap_bytes_moved,
            restore_s=self.restore_s,
            recompute_tokens_avoided=self.recompute_tokens_avoided,
            host_tier_hits=self.blocks.host_tier_hits,
            host_tier_hit_rate=(
                self.blocks.host_tier_hits
                / max(1, self.blocks.host_tier_lookups)
                if self.swap != "off" else None),
            migrations_in=self.migrations_in,
            migrations_out=self.migrations_out,
            migration_bytes=self.migration_bytes,
            state_bytes_per_slot=(self.state_bytes_per_slot
                                  if self._stateful else None),
            state_pool_bytes=(self.state_pool_bytes
                              if self._stateful else None),
            state_slots_peak=(self.peak_resident
                              if self._stateful else None),
            state_step=(self.model.state_step()
                        if self._stateful else None),
            state_chunk=(self.model.state_chunk()
                         if self._stateful else None))

    @property
    def prefix_cache_state(self):
        """``prefix_cache`` as ``stats()``, ``slo_summary()`` and the
        ``report`` event say it: the option's bool, or for a model with
        recurrent state, whose prefix index stands down, the reason."""
        if self.prefix_cache and self._stateful:
            return "off (recurrent state)"
        return self.prefix_cache

    def _aggregate_hit_rate(self) -> Optional[float]:
        """Prompt tokens served from cache / prompt tokens admitted,
        over every finished request (None with prefix caching off or
        before any finish)."""
        if not self.prefix_cache:
            return None
        admitted = sum(r.prefix_prompt_tokens
                       for r in self.finished.values())
        if not admitted:
            return None
        return (sum(r.prefix_cached_tokens
                    for r in self.finished.values()) / admitted)

    # -- one engine iteration ------------------------------------------------

    def step(self) -> None:
        """Admit → batched prefill under the token budget → one decode
        step over all slots at the iteration's gather bucket. With
        ``timeline`` on, every phase transition is stamped host-side
        (queue→prefill at admission, preemption intervals at eviction)
        and one ``iteration_ledger`` event records the iteration's
        phase mix — all ``perf_counter`` arithmetic, zero new compiled
        variants.

        With ``overlap`` on (the default) the decode tail of the
        iteration runs DISPATCH-AHEAD: the admission/prefill/stamping
        above already executed concurrently with the previous
        iteration's in-flight device step, and the plain families
        dispatch iteration N before committing N−1's (already
        computed) tokens — see :meth:`_dispatch_decode` /
        :meth:`_commit_decode`. A speculative engine commits its
        in-flight window first (:meth:`_commit_spec`) because the next
        window's inputs are data-dependent on the acceptance counts.

        Under a tensor-parallel mesh (ISSUE 13) the whole iteration
        runs inside ``use_mesh`` — the ambient mesh model code (and
        the gathered-view head pinning in ``ops.attention``) keys on;
        every dispatch's SPMD partitioning is otherwise driven by the
        committed param/pool shardings alone."""
        with self._mesh_ctx():
            self._step()

    def _mesh_ctx(self):
        return _mesh_context(self.mesh)

    def _step(self) -> None:
        t_iter0 = self._t_lap = time.perf_counter()
        gap_s = (0.0 if self._t_step_end is None
                 else t_iter0 - self._t_step_end)
        parts = self._iter_parts
        parts[:] = (0.0, 0.0, 0.0, 0.0)
        tokens0 = self.tokens_generated
        chunks0, disp0 = self.prefill_chunks, self.prefill_dispatches
        needed0 = self.prefill_keys_needed
        attended0 = self.prefill_keys_attended
        self._iter_prefill_s = 0.0
        self._iter_decode_s = 0.0
        self._iter_decode_slots = 0
        self._iter_state_slots = 0
        self._iter_kv_resident = 0
        self._iter_prefill_tokens = 0
        sink = obs.has_sink()
        with obs.span("serve/step",
                      {"iteration": self.iterations} if sink else None):
            with obs.span("serve/admit"):
                for slot in self.sched.admit():
                    n_cow = len(slot.pending_copies)
                    if self.timeline:
                        # stamp BEFORE the COW copies run: the
                        # queue/preempted interval ends at admission,
                        # and the copy dispatches land in overhead (the
                        # documented contract)
                        self._stamp_admit(slot, n_cow)
                    self._apply_restores(slot)
                    self._apply_cow(slot)
                    if sink:
                        extra = {}
                        if self.prefix_cache:
                            extra["prefix_cached_tokens"] = slot.prefill_pos
                        obs.serve("admit", request=slot.request.rid,
                                  slot=slot.index,
                                  queue_depth=len(self.sched.waiting),
                                  **self._replica_kw(),
                                  **self._trace_kw(slot.request), **extra)
                if self.timeline and self.sched.waiting:
                    # admission-block attribution: only the policy's
                    # TOP-RANKED candidate is ever capacity-blocked
                    # (everyone behind it is blocked BY it) — under fifo
                    # that is the queue head, under slo the ranked front
                    # — name why it is still waiting
                    head = self.sched.blocked_head()
                    head.blocked_iters += 1
                    head.blocked_reason = (
                        "no_free_slot"
                        if all(not s.free for s in self.sched.slots)
                        else "kv_capacity")
                self.peak_resident = max(
                    self.peak_resident,
                    sum(1 for s in self.sched.slots if not s.free))
            self._lap(_STAGE)
            C = self.sched.prefill_chunk
            budget = self.sched.prefill_token_budget(
                len(self.sched.decode_slots()))
            while budget >= C:
                # charged at DISPATCH cost (incl. pad rows of a partially
                # filled batch), not real chunks — the budget bounds the
                # decode stall, and the stall is what the device computes
                dispatched_rows = self._prefill_batch(budget // C)
                if not dispatched_rows:
                    break
                budget -= dispatched_rows * C
            if self.prefill_only:
                # disaggregated prefill replica (ISSUE 18): no decode phase
                # at all — no capacity math either, since parked DECODE
                # slots never grow their tables here (the router migrates
                # them to a decode replica between iterations, and "zero
                # decode iterations on a prefill replica" is the bench's
                # role-separation gate)
                pass
            elif not self.overlap:
                self._capacity_phase()
                self._decode_all()
            elif self.speculative:
                # the in-flight window overlapped the admission/prefill
                # work above; it must land before the capacity math (the
                # context advance is data-dependent) and the next dispatch
                self._commit_spec(self._pending_spec)
                self._pending_spec = None
                self._capacity_phase()
                self._pending_spec = self._dispatch_spec()
            else:
                # plain/bucketed/kernel families: flush the pipeline only
                # when the capacity math could preempt (the recompute path
                # must see committed state), dispatch N, then commit N−1's
                # tokens while N runs on the device
                if (self._pending is not None
                        and not self._capacity_covered()):
                    self._flush("kv_pressure")
                self._capacity_phase()
                if self._lone_stream():
                    # low-load auto-flush (ISSUE 13, the PR 12 TTFT
                    # follow-up): a LONE stream with nothing waiting has
                    # no concurrent host work for the pipeline to hide —
                    # dispatch-ahead would only defer every token's fetch
                    # (and the final token's delivery) by one iteration.
                    # Run this iteration serially instead: land any
                    # in-flight dispatch (a plain commit, not a forced
                    # drain — overlap_flushes counts mandatory drains
                    # only), then dispatch+fetch in one go, exactly the
                    # overlap='off' schedule. The condition re-evaluates
                    # every iteration, so the pipeline re-engages the
                    # moment a second stream admits.
                    prev, self._pending = self._pending, None
                    self._commit_decode(prev)
                    self._decode_all()
                else:
                    prev, self._pending = (self._pending,
                                           self._dispatch_decode())
                    self._commit_decode(prev)
        # per-iteration scheduler gauges (SLO telemetry): queue pressure
        # and slot occupancy as series, one sample per engine iteration
        waiting = len(self.sched.waiting)
        self.peak_waiting = max(self.peak_waiting, waiting)
        arrival_kw = {}
        if self._has_arrivals:
            # open-loop backlog (ISSUE 16): how many arrival-stamped
            # requests are queued at this instant — a deterministic
            # integer (unlike the wall-time queue decomposition), so
            # the virtual-clock bench can gate on it. Absent entirely
            # on closed-loop runs — the byte-identity contract.
            backlog = sum(1 for r in self.sched.waiting
                          if r.arrival_s is not None)
            self._arrival_backlog_peak = max(
                self._arrival_backlog_peak, backlog)
            arrival_kw["arrival_backlog"] = backlog
        # the iteration ends here: what follows (the ledger's own
        # write) falls into the next iteration's gap_s, so dur_s and
        # gap_s together tile the loop's wall time
        t_end = self._t_step_end = time.perf_counter()
        dur_s = t_end - t_iter0
        totals = self._host_totals
        totals[_STAGE] += parts[_STAGE]
        totals[_DISPATCH] += parts[_DISPATCH]
        totals[_FETCH] += parts[_FETCH]
        totals[_COMMIT] += parts[_COMMIT]
        totals[_DUR] += dur_s
        totals[_GAP] += gap_s
        moe_kw = self._moe_resolve() if sink else {}
        # absent from the ledger of a model without recurrent state
        state_kw = dict(state_slots=self._iter_state_slots,
                        kv_tokens_resident=self._iter_kv_resident,
                        prefill_tokens=self._iter_prefill_tokens,
                        state_slots_peak=self.peak_resident) \
            if self._stateful else {}
        if sink and self.timeline:
            # the engine ledger: one line per iteration with the phase
            # mix (prefill vs decode dispatch seconds inside the
            # iteration wall), the iteration's account of its wall time
            # (`_lap`), the bucket, the slot/token throughput, and
            # queue and pool pressure — what `obsctl tail` follows live
            obs.serve(
                "iteration_ledger", iteration=self.iterations,
                dur_s=round(dur_s, 6),
                prefill_s=round(self._iter_prefill_s, 6),
                decode_s=round(self._iter_decode_s, 6),
                stage_s=round(parts[_STAGE], 6),
                dispatch_s=round(parts[_DISPATCH], 6),
                fetch_wait_s=round(parts[_FETCH], 6),
                commit_s=round(parts[_COMMIT], 6),
                gap_s=round(gap_s, 6),
                gather_bucket=self._bucket,
                prefill_chunks=self.prefill_chunks - chunks0,
                prefill_dispatches=self.prefill_dispatches - disp0,
                prefill_keys_needed=self.prefill_keys_needed - needed0,
                prefill_keys_attended=(self.prefill_keys_attended
                                       - attended0),
                decode_slots=self._iter_decode_slots,
                tokens=self.tokens_generated - tokens0,
                waiting=waiting,
                preemptions=self.sched.n_preemptions,
                kv_used_frac=round(self.blocks.utilization(), 4),
                **moe_kw, **state_kw, **arrival_kw, **self._replica_kw())
        elif sink:
            # timeline off: the per-iteration gauges as series, which
            # `obsctl tail` falls back to (with it on, the ledger line
            # above carries all four)
            obs.scalar("serve/waiting_depth", waiting, self.iterations)
            obs.scalar("serve/running_slots",
                       len(self.sched.decode_slots()), self.iterations)
            obs.scalar("serve/preemptions", self.sched.n_preemptions,
                       self.iterations)
            obs.scalar("serve/gather_bucket", self._bucket,
                       self.iterations)
        self.iterations += 1


    def _latent_kw(self, q_len: int, width: Optional[int] = None) -> dict:
        """``{"latent_path": "absorbed" | "expanded"}`` for a dispatch
        of ``q_len`` queries a row of a latent-attention model (the
        model's own rule on the shape), and for an expanded one over
        ``width`` keys ``"expanded_form": "kernel" | "xla_loop"`` (the
        model's own rule on the shapes and the backend, the one its
        trace follows), and beside them what a model with a residual path
        of its own says of itself (``residual_streams``, ``gate``); ``{}``
        for any other model."""
        out = dict(self._residual_kw)
        if self._latent:
            out["latent_path"] = self.model.latent_path(q_len)
            if out["latent_path"] == "expanded":
                out["expanded_form"] = self.model.expanded_form(q_len, width)
        return out

    def _state_kw(self, q_len: int, rows: int) -> dict:
        """``{"state_form": "step" | "chunked", "state_rows": n}`` for a
        dispatch of ``q_len`` tokens a row of a model with recurrent
        state, ``rows`` of them real (their state is read and written),
        and for a one-token step ``"state_step": "kernel" | "xla"``, for a
        run of tokens ``"state_chunk": "kernel" | "xla"`` (the model's own
        rule on the shapes and the backend: the fused kernel or the XLA
        form); ``{}`` for any other model."""
        if not self._stateful:
            return {}
        out = {"state_form": self.model.state_form(q_len),
               "state_rows": rows}
        if out["state_form"] == "step":
            out["state_step"] = self.model.state_step()
        else:
            out["state_chunk"] = self.model.state_chunk()
        return out

    def _state_args(self, rows=None) -> tuple:
        """The state operands of a step of a model with recurrent state
        (the pools, and for a prefill dispatch each row's pool row);
        ``()`` for any other model, whose call is the one it always
        was."""
        if not self._stateful:
            return ()
        return (self._states,) if rows is None else (self._states, rows)

    def _step_out(self, out: tuple) -> tuple:
        """``(token array, routed counts)`` of a step's result, the block
        pools and the state pools it returned kept as the engine's."""
        tok, self._pools, *rest = out
        if self._stateful:
            self._states = rest.pop()
        return tok, rest

    def _moe_dispatched(self, moe: list, tokens: int, decode: bool) -> None:
        """Keep a dispatch's routed counts (a device array, NOT fetched
        here) with the pairs its ``tokens`` real tokens made, when there
        is a sink to report them to; an untraced run drops them."""
        if moe and obs.has_sink():
            self._moe_flight.append(
                (tokens * self._moe_fanout, moe[0], decode, *moe[1:]))

    def _moe_mark(self) -> int:
        """How many routed-count entries the run has made so far: the
        device runs its dispatches in order, so a fetch of the newest
        one's result shows all of them computed."""
        return self._moe_resolved + len(self._moe_flight)

    def _moe_resolve(self, everything: bool = False) -> dict:
        """Fetch the routed counts of the dispatches a later fetch has
        shown computed (all that are left with ``everything``: the run
        is over), add them to the running sums and return the ledger's
        fields: ``moe_pairs`` / ``moe_pairs_held`` of those dispatches,
        the decode steps' part of both (``moe_decode_pairs`` /
        ``moe_decode_pairs_held``) and, per expert layer, of the last
        decode step among them, the held experts that got a pair and the
        busiest and the mean held expert's pairs; for a model that sows it,
        ``mhc_defect_max`` of those dispatches. ``{}`` for a model that
        routes nothing."""
        if not self._routes:
            return {}
        n = (len(self._moe_flight) if everything
             else self._moe_landed - self._moe_resolved)
        landed, self._moe_flight = self._moe_flight[:n], self._moe_flight[n:]
        self._moe_resolved += n
        self._moe_landed = max(self._moe_landed, self._moe_resolved)
        out = {"moe_pairs": 0, "moe_pairs_held": 0,
               "moe_decode_pairs": 0, "moe_decode_pairs_held": 0}
        for pairs, counts, decode, *defect in landed:
            counts = np.asarray(counts)          # computed: no wait
            if defect:
                # hyper-connections: the largest |row or column sum - 1|
                # of any H_res these dispatches made
                out["mhc_defect_max"] = max(out.get("mhc_defect_max", 0.0),
                                            float(defect[0]))
            held = int(counts.sum())
            out["moe_pairs"] += pairs
            out["moe_pairs_held"] += held
            if decode:
                out["moe_decode_pairs"] += pairs
                out["moe_decode_pairs_held"] += held
                out["moe_experts_touched"] = (counts > 0).sum(1).tolist()
                out["moe_expert_load_max"] = counts.max(1).tolist()
                out["moe_expert_load_mean"] = [
                    round(float(m), 4) for m in counts.mean(1)]
        self.moe_pairs += out["moe_pairs"]
        self.moe_pairs_held += out["moe_pairs_held"]
        return out

    def _capacity_phase(self) -> None:
        """Decode-side block capacity for the next dispatch, preempting
        when the pool runs dry (serial semantics — under overlap the
        caller drained the pipeline first when this could preempt)."""
        with obs.span("serve/capacity"):
            for req in self.sched.ensure_decode_capacity():
                if obs.has_sink():
                    obs.serve("preempt", request=req.rid,
                              reason="kv_pool_exhausted",
                              **self._replica_kw(), **self._trace_kw(req))
                if self.timeline:
                    # the preempted interval runs from here to
                    # re-admission; emit the partial timeline NOW so a
                    # request that never comes back (a killed run) still
                    # left its history
                    req.preempt_t = time.perf_counter()
                    self._emit_timeline(req, "preempt", req.preempt_t)
        self._lap(_STAGE)

    def _lone_stream(self) -> bool:
        """True when decode-batch occupancy is exactly one and the
        waiting queue is empty — the dispatch-ahead pipeline's
        auto-flush condition (ISSUE 13): the single resident stream is
        decoding, no other slot is prefilling alongside it and nothing
        is queued, so there is no concurrent host work to overlap and
        the deferred fetch would be pure added latency per token."""
        busy = [s for s in self.sched.slots if not s.free]
        return (not self.sched.waiting and len(busy) == 1
                and busy[0].request is not None
                and busy[0].request.state == DECODE)

    def _capacity_covered(self) -> bool:
        """True when every decode slot's next write span is coverable
        without touching the preemption path — the cheap host-side
        precheck that decides whether the dispatch-ahead pipeline must
        drain before :meth:`_capacity_phase` runs. Conservative: a
        False here only costs one lost overlap window."""
        need = sum(
            max(0, self.blocks.blocks_for(
                s.context_len + self.sched.decode_lookahead)
                - len(s.table))
            for s in self.sched.decode_slots())
        return self.blocks.can_allocate(need)

    def _flush(self, reason: str) -> None:
        """Drain the dispatch-ahead pipeline: fetch and commit the
        in-flight iteration NOW (losing its overlap window) so the
        caller's next decision acts on fully committed state. The
        mandatory drains — preemption and KV-pressure block math — are
        what ``overlap_flushes`` counts."""
        if self._pending is None:
            return
        self.overlap_flushes += 1
        prev, self._pending = self._pending, None
        self._commit_decode(prev)

    def _select_bucket(self, need: int) -> int:
        """Smallest configured bucket covering ``need`` resident
        context, with shrink hysteresis: growth is immediate
        (correctness — the write position must be addressable),
        shrinking waits ``SHRINK_PATIENCE`` consecutive iterations
        where the smaller bucket would have sufficed, so churn around
        a boundary stays bounded. Every switch is telemetered."""
        fit = next(b for b in self.gather_buckets if b >= need)
        if fit > self._bucket:
            self._switch_bucket(fit, need)
        elif fit < self._bucket:
            self._shrink_streak += 1
            if self._shrink_streak >= self.SHRINK_PATIENCE:
                self._switch_bucket(fit, need)
        else:
            self._shrink_streak = 0
        return self._bucket

    def _switch_bucket(self, new: int, need: int) -> None:
        prev, self._bucket = self._bucket, new
        self._shrink_streak = 0
        self.bucket_switches += 1
        obs.serve("bucket_switch", gather_bucket=new, prev_bucket=prev,
                  max_context=need)

    def _prefill_batch(self, max_rows: int) -> int:
        """One batched prefill dispatch over up to
        ``min(max_rows, prefill_batch)`` prefilling slots (static
        [G, C] shape — unused rows ride to the null block), at the
        smallest gather bucket that holds ``max(start) + C`` over its
        rows: the chunk attends the keys its context needs, not
        ``max_model_len`` (no hysteresis, no state: each dispatch picks
        its own bucket). A LONE prefilling request whose context fits
        the first bucket runs the [1, C] variant instead: padding it
        to the full batch would multiply low-load prefill compute (and
        TTFT) by ``prefill_batch``. Past the first bucket a lone row
        rides the batched shape at its bucket (the [1, C] shape exists
        at the first bucket only: at full width it lost to the batched
        one on the v5e, 498 ms against 300 ms for four rows, PERF.md
        §6). So ``len(ladder) + 1`` compiled shapes, all warmed.
        Returns the DISPATCHED row count G — pad rows included, so the
        caller's token budget charges what the device actually
        computed, keeping the decode-stall bound honest at partial
        load (0 = no prefill work)."""
        slots = self.sched.next_prefill_slots(
            min(max_rows, self.prefill_batch))
        if not slots:
            return 0
        with obs.span("serve/stage_prefill"):
            C = self.sched.prefill_chunk
            need = max(slot.prefill_pos for slot in slots) + C
            width = next(b for b in self.prefill_buckets if b >= need)
            G = (1 if len(slots) == 1 and width == self.prefill_buckets[0]
                 else self.prefill_batch)
            chunks = np.full((G, C), self.pad_token_id, np.int32)
            tables = np.zeros((G, self.max_blocks_per_seq), np.int32)
            start = np.zeros((G,), np.int32)
            rel = np.full((G,), -1, np.int32)
            temps = np.zeros((G,), np.float32)
            top_ks = np.zeros((G,), np.int32)
            top_ps = np.zeros((G,), np.float32)
            keys = np.zeros((G, 2), np.uint32)
            folds = np.zeros((G,), np.int32)
            # each row's row of the state pools: its slot's; a pad row
            # names none (num_slots: it reads zeros, its write is dropped)
            rows = np.full((G,), self.num_slots, np.int32)
            rows[:len(slots)] = [slot.index for slot in slots]
            finals = []
            sampled = False
            for i, slot in enumerate(slots):
                req = slot.request
                pos = slot.prefill_pos
                real = req.prompt[pos:pos + C]
                chunks[i, :len(real)] = real
                tables[i, :len(slot.table)] = slot.table
                start[i] = pos
                if pos + C >= self.sched.padded_prompt_len(req):
                    rel[i] = (len(req.prompt) - 1) - pos
                    finals.append((i, slot))
                    if req.sampled:
                        sampled = True
                        temps[i] = req.temperature
                        top_ks[i] = req.top_k
                        top_ps[i] = req.top_p
                        keys[i] = self._keys[req.rid]
                        folds[i] = self._generated(req)
        t0 = self._lap(_STAGE)
        # the dispatch's real prompt tokens (a final chunk may be short)
        real_tokens = sum(min(C, len(s.request.prompt) - s.prefill_pos)
                          for s in slots)
        latent_kw = self._latent_kw(C, width)
        with obs.span("serve/prefill_chunk",
                      {"chunks": len(slots), "rows": G, "width": width,
                       "write_path": self.write_path,
                       **latent_kw, **self._state_kw(C, len(slots))}
                      if obs.has_sink() else None):
            tok, moe = self._step_out(self._prefill_fn(
                self.model, self.params, self._pools, chunks, tables,
                start, rel, temps, top_ks, top_ps, keys, folds,
                self._plan, sampled, width, *self._state_args(rows)))
            if self._stateful:
                self._iter_state_slots += len(slots)
                self._iter_prefill_tokens += real_tokens
            self._moe_dispatched(moe, real_tokens, False)
            if self.speculative:
                # the draft's pools must hold the prompt KV too — same
                # chunks/tables, its own address space; the returned
                # token is discarded (the draft never emits)
                _, self._d_pools, *_ = self._prefill_fn(
                    self.draft_model, self.draft_params, self._d_pools,
                    chunks, tables, start, rel, temps, top_ks, top_ps,
                    keys, folds, self._d_plan, False, width)
        dur = self._lap(_DISPATCH) - t0
        if self.timeline:
            # dispatch-enqueue wall time (an async backend's device
            # wait surfaces at the next sync and lands in overhead —
            # attribution stays disjoint, never double-counted)
            self._iter_prefill_s += dur
            for slot in slots:
                self._accrue_prefill(slot, t0, dur)
        for slot in slots:
            slot.prefill_pos += C
            self.prefill_keys_needed += slot.prefill_pos
        self.prefill_chunks += len(slots)
        self.prefill_dispatches += 1
        if self._latent:
            self.prefill_dispatches_by_form[latent_kw["expanded_form"]] += 1
        self.prefill_keys_attended += G * width
        if finals:
            self._lap(_COMMIT)
            # fetch the continuation tokens; also the sync point that
            # makes TTFT an honest end-to-end wall time
            with obs.span("serve/first_token_fetch"):
                # graftlint: allow[R2] first-token fetch at prompt completion: the value gates the slot's prefill->decode flip and is the sync that keeps TTFT an honest wall time
                tok_host = np.asarray(jax.device_get(tok))
            self._moe_landed = self._moe_mark()
            self._lap(_FETCH)
            with obs.span("serve/commit"):
                for i, slot in finals:
                    req = slot.request
                    self.sched.finish_prefill(slot)
                    if self.speculative and self._generated(req) > 0:
                        # preemption-resumed speculative request: its
                        # next token's index is mid-stream, and
                        # mid-stream tokens come from verify windows —
                        # emitting the prefill sample here would consume
                        # a different RNG draw than the uninterrupted
                        # run's window did (breaking bitwise
                        # seed-reproducibility across preemption). Hand
                        # the slot to the window loop instead: its
                        # newest committed token is the folded prompt's
                        # last id, whose K/V the next window re-writes
                        # at context_len (same value the prefill just
                        # wrote — an idempotent overwrite)
                        slot.context_len -= 1
                    else:
                        self._append(slot, int(tok_host[i]))
        self._lap(_COMMIT)
        return G

    def _decode_all(self) -> None:
        if self.speculative:
            return self._decode_all_spec()
        ds = self.sched.decode_slots()
        if not ds:
            return
        with obs.span("serve/stage_decode"):
            bucket = self._select_bucket(self.sched.max_decode_context())
            S = self.num_slots
            tokens = np.zeros((S,), np.int32)
            tables = np.zeros((S, self.max_blocks_per_seq), np.int32)
            ctx = np.zeros((S,), np.int32)
            active = np.zeros((S,), bool)
            temps = np.zeros((S,), np.float32)
            top_ks = np.zeros((S,), np.int32)
            top_ps = np.zeros((S,), np.float32)
            keys = np.zeros((S, 2), np.uint32)
            folds = np.zeros((S,), np.int32)
            sampled = False
            for slot in ds:
                req = slot.request
                i = slot.index
                tokens[i] = req.output[-1]
                tables[i, :len(slot.table)] = slot.table
                ctx[i] = slot.context_len
                active[i] = True
                if req.sampled:
                    sampled = True
                    temps[i] = req.temperature
                    top_ks[i] = req.top_k
                    top_ps[i] = req.top_p
                    keys[i] = self._keys[req.rid]
                    folds[i] = self._generated(req)
            self.blocks.note_gather([s.context_len + 1 for s in ds], bucket)
            # the step's KV read traffic in POOL bytes (every slot row of
            # the dispatch × the bucket width × bytes/token across pools —
            # int8 pools halve this, which is the point): a running sum
            # the SLO report carries
            step_bytes = self.num_slots * bucket * self.blocks.token_bytes
            self.kv_bytes_read += step_bytes
            # blocks_saved() == 0 means no block is shared right now — the
            # per-slot table walk would only accumulate zeros, so skip it
            # (the common case for non-templated traffic with the cache on)
            if self.prefix_cache and self.blocks.blocks_saved() > 0:
                self.blocks.note_shared_reads(sum(
                    self.blocks.shared_read_tokens(s.table, s.context_len)
                    for s in ds))
        t0 = self._lap(_STAGE)
        with obs.span("serve/decode_step",
                      {"active": len(ds), "gather_bucket": bucket,
                       "decode_path": self.decode_path,
                       **self._latent_kw(1), **self._state_kw(1, len(ds))}
                      if obs.has_sink() else None):
            nxt, moe = self._step_out(self._decode_fn(
                self.model, self.params, self._pools, tokens, tables,
                ctx, active, temps, top_ks, top_ps, keys, folds,
                self._plan, bucket, sampled, *self._state_args()))
            if self._stateful:
                self._iter_state_slots += len(ds)
                self._iter_kv_resident += int(ctx.sum()) + len(ds)
            self._moe_dispatched(moe, len(ds), True)
            self._lap(_DISPATCH)
            with obs.span("serve/commit_fetch"):
                # graftlint: allow[R2] the SERIAL loop's per-step fetch: this is the overlap=off reference implementation the dispatch-ahead gates compare against, serial by definition
                nxt = np.asarray(jax.device_get(nxt))
            self._moe_landed = self._moe_mark()
        dur = self._lap(_FETCH) - t0
        self.decode_time_s += dur
        self.decode_steps += 1
        self.decode_tokens += len(ds)
        if self.timeline:
            self._iter_decode_s += dur
            self._iter_decode_slots = len(ds)
        with obs.span("serve/commit"):
            for slot in ds:
                slot.context_len += 1    # the fed token's K/V landed
                if self.timeline:
                    self._accrue_decode(slot.request, t0, dur, bucket, 1)
                self._append(slot, int(nxt[slot.index]))
        self._lap(_COMMIT)

    def _dispatch_decode(self) -> Optional[_PendingDecode]:
        """Dispatch-ahead plain decode (ISSUE 12): enqueue iteration N
        WITHOUT waiting for iteration N−1's tokens. A rider of the
        in-flight dispatch feeds its un-fetched DEVICE token (the
        pipeline's data chain — the value never round-trips through
        the host); slots whose newest token is host-known (fresh from
        prefill, first step after a flush) merge in through the warmed
        fixed-shape select. Slots that will BUDGET-finish when N−1
        commits are excluded up front (a pure count — re-derived
        exactly, no token value needed); an EOS finish is unknowable
        here, so that rider runs one wasted row whose output the
        commit discards — the stale K/V write is hidden by the
        context masks and ordered before any block reuse by the pool
        chain. Context lengths advance AT DISPATCH (the write lands
        regardless of the token's value), which keeps bucket choice
        and block math exact, not speculative.

        The per-slot staging/accounting here deliberately MIRRORS
        :meth:`_decode_all` instead of replacing it: the serial loop
        stays an INDEPENDENT reference implementation, which is what
        gives the overlap-on == overlap-off torture gates their teeth
        (shared code would compare a path against itself). Accounting
        changes must land in both."""
        prev = self._pending
        ds = []
        for slot in self.sched.decode_slots():
            eff = self._generated(slot.request) + slot.inflight
            if eff >= slot.request.max_new_tokens:
                continue         # finishes at the in-flight commit
            ds.append(slot)
        if not ds:
            return None
        with obs.span("serve/stage_decode"):
            bucket = self._select_bucket(
                max(s.context_len + self.sched.decode_lookahead
                    for s in ds))
            S = self.num_slots
            vals = np.zeros((S,), np.int32)
            use_dev = np.zeros((S,), bool)
            tables = np.zeros((S, self.max_blocks_per_seq), np.int32)
            ctx = np.zeros((S,), np.int32)
            active = np.zeros((S,), bool)
            temps = np.zeros((S,), np.float32)
            top_ks = np.zeros((S,), np.int32)
            top_ps = np.zeros((S,), np.float32)
            keys = np.zeros((S, 2), np.uint32)
            folds = np.zeros((S,), np.int32)
            sampled = False
            for slot in ds:
                req = slot.request
                i = slot.index
                if slot.inflight:
                    use_dev[i] = True
                else:
                    # a DECODE slot always has output resident (prefill
                    # appends the first token before the state flips) —
                    # same invariant the serial loop indexes on
                    vals[i] = req.output[-1]
                tables[i, :len(slot.table)] = slot.table
                ctx[i] = slot.context_len
                active[i] = True
                if req.sampled:
                    sampled = True
                    temps[i] = req.temperature
                    top_ks[i] = req.top_k
                    top_ps[i] = req.top_p
                    keys[i] = self._keys[req.rid]
                    # the in-flight token counts: token N's fold index is
                    # its request-global position, exactly the serial value
                    folds[i] = self._generated(req) + slot.inflight
            self.blocks.note_gather([s.context_len + 1 for s in ds], bucket)
            step_bytes = self.num_slots * bucket * self.blocks.token_bytes
            self.kv_bytes_read += step_bytes
            if self.prefix_cache and self.blocks.blocks_saved() > 0:
                self.blocks.note_shared_reads(sum(
                    self.blocks.shared_read_tokens(s.table, s.context_len)
                    for s in ds))
            if prev is None or not use_dev.any():
                tokens = vals
            elif all(s.inflight for s in ds):
                # steady pipeline: every active slot rode the in-flight
                # dispatch, so its token array IS the feed — no select op
                # on the device chain at all (the common decode-bound case)
                tokens = prev.nxt
            else:
                tokens = jnp.where(use_dev, prev.nxt, vals)
        t0 = self._lap(_STAGE)
        with obs.span("serve/decode_step",
                      {"active": len(ds), "gather_bucket": bucket,
                       "decode_path": self.decode_path,
                       **self._latent_kw(1), **self._state_kw(1, len(ds))}
                      if obs.has_sink() else None):
            nxt, moe = self._step_out(self._decode_fn(
                self.model, self.params, self._pools, tokens, tables,
                ctx, active, temps, top_ks, top_ps, keys, folds,
                self._plan, bucket, sampled, *self._state_args()))
            if self._stateful:
                self._iter_state_slots += len(ds)
                self._iter_kv_resident += int(ctx.sum()) + len(ds)
            self._moe_dispatched(moe, len(ds), True)
        dispatch_s = self._lap(_DISPATCH) - t0
        if self.timeline:
            # the enqueue cost lands in THIS iteration's ledger (the
            # blocked fetch lands in the committing iteration's), so
            # dur_s >= prefill_s + decode_s stays true per ledger line
            self._iter_decode_s += dispatch_s
        for slot in ds:
            slot.context_len += 1        # the fed token's K/V lands
            slot.inflight = 1
        return _PendingDecode(nxt, tuple((s, s.request) for s in ds),
                              bucket, dispatch_s, t0, self._moe_mark())

    def _commit_decode(self, prev: Optional[_PendingDecode]) -> None:
        """Land one in-flight plain decode iteration: the deferred
        ``device_get`` — by now the device has computed through all
        the host work since dispatch, so the blocked wait is only the
        residual — then append/EOS-check per rider. Decode time
        accounts dispatch enqueue + blocked fetch ONLY: the host work
        in between ran concurrently with the device, which is the
        measurable claim of the dispatch-ahead loop. A rider whose
        request finished at the previous commit (EOS discovered one
        step late) has its token discarded — a serial loop would
        never have computed it, and discarding reproduces the serial
        output exactly."""
        if prev is None:
            return
        t0 = self._lap(_STAGE)
        with obs.span("serve/commit_fetch"):
            # graftlint: allow[R2] THE deferred commit fetch (ISSUE 12): deliberately one iteration late, so only the residual past the overlapped host work blocks here
            nxt = np.asarray(prev.nxt)
        self._moe_landed = max(self._moe_landed, prev.moe_seq)
        t_end = self._lap(_FETCH)
        fetch_s = t_end - t0
        # the ENGINE's decode-time accounting stays blocked-time only
        # (dispatch enqueue + residual fetch wait): the host work in
        # between ran concurrently, and hiding it is exactly what the
        # bench's decode-tokens/sec ratio measures
        self.decode_time_s += prev.dispatch_s + fetch_s
        self.decode_steps += 1
        # riders of the CURRENT in-flight dispatch keep their inflight
        # mark (dispatch N ran before this commit of N−1 and re-marked
        # them); everyone else's newest token is host-resident again
        with obs.span("serve/commit"):
            live = {id(s) for s, _ in (self._pending.riders
                                       if self._pending is not None else ())}
            committed = 0
            for slot, req in prev.riders:
                if id(slot) not in live:
                    slot.inflight = 0
                if req.rid in self.finished or slot.request is not req:
                    continue         # wasted row past an EOS: discarded
                committed += 1
                self.decode_tokens += 1
                if self.timeline:
                    # the REQUEST's decode interval is the whole
                    # dispatch→fetch window — the host work inside it ran
                    # concurrently with the device, so it is decode time,
                    # not overhead — clipped to the request's previous
                    # attributed end so intervals stay disjoint (the
                    # checkable-decomposition invariant): back-to-back
                    # overlapped iterations tile the decode-bound stretch
                    # with no overhead gaps, which is the decomposition's
                    # view of the de-overheaded loop
                    start = prev.t_dispatch
                    if req.decode_attr_end is not None:
                        start = max(start, req.decode_attr_end)
                    self._accrue_decode(req, start, t_end - start,
                                        prev.bucket, 1)
                    req.decode_attr_end = t_end
                self._append(slot, int(nxt[slot.index]))
        if self.timeline:
            self._iter_decode_s += fetch_s
            self._iter_decode_slots = committed
        self._lap(_COMMIT)

    def _decode_all_spec(self) -> None:
        """One SERIAL speculative iteration: dispatch + immediate
        commit (the dispatch-ahead loop splits these across the
        iteration boundary instead, overlapping the next iteration's
        admission/prefill/telemetry with the in-flight window)."""
        self._commit_spec(self._dispatch_spec())

    def _dispatch_spec(self) -> Optional[_PendingSpec]:
        """Enqueue one speculative draft-k propose + width-(k+1)
        verify dispatch over all decode slots; the host-side commit
        (:meth:`_commit_spec`) lands the accepted prefix + bonus per
        slot — ``context_len`` advanced over exactly the committed
        tokens (the O(1) rewind: rejected draft K/V past it is stale,
        invisible to context-derived masks, and overwritten by the
        next window), and the block-table tail past the committed
        context returns to the free list."""
        ds = self.sched.decode_slots()
        if not ds:
            return None
        with obs.span("serve/stage_decode"):
            k = self.speculate_k
            bucket = self._select_bucket(self.sched.max_decode_context())
            S = self.num_slots
            tokens = np.zeros((S,), np.int32)
            tables = np.zeros((S, self.max_blocks_per_seq), np.int32)
            ctx = np.zeros((S,), np.int32)
            active = np.zeros((S,), bool)
            temps = np.zeros((S,), np.float32)
            top_ks = np.zeros((S,), np.int32)
            top_ps = np.zeros((S,), np.float32)
            keys = np.zeros((S, 2), np.uint32)
            folds = np.zeros((S,), np.int32)
            sampled = False
            for slot in ds:
                req = slot.request
                i = slot.index
                # newest committed token: the last generated one, or the
                # prompt tail when no generation is resident in `output`
                # (fresh post-preemption resume)
                tokens[i] = req.output[-1] if req.output else req.prompt[-1]
                tables[i, :len(slot.table)] = slot.table
                ctx[i] = slot.context_len
                active[i] = True
                if req.sampled:
                    sampled = True
                    temps[i] = req.temperature
                    top_ks[i] = req.top_k
                    top_ps[i] = req.top_p
                    keys[i] = self._keys[req.rid]
                    folds[i] = self._generated(req)   # window start index
            self.blocks.note_gather(
                [s.context_len + k + 1 for s in ds], bucket)
            # draft (k+1 steps) + verify each read a bucket-wide assembled
            # cache: the target-pool read is what the fp-vs-int8 comparison
            # isolates, so account the verify read (one bucket per slot row)
            step_bytes = self.num_slots * bucket * self.blocks.token_bytes
            self.kv_bytes_read += step_bytes
            if self.prefix_cache and self.blocks.blocks_saved() > 0:
                self.blocks.note_shared_reads(sum(
                    self.blocks.shared_read_tokens(s.table, s.context_len)
                    for s in ds))
        t0 = self._lap(_STAGE)
        with obs.span("serve/spec_decode_step",
                      {"active": len(ds), "gather_bucket": bucket,
                       "decode_path": self.decode_path,
                       "speculate_k": k} if obs.has_sink() else None):
            drafts, n_acc, bonus, self._pools, self._d_pools = \
                self._spec_fn(
                    self.model, self.params, self.draft_model,
                    self.draft_params, self._pools, self._d_pools,
                    tokens, tables, ctx, active, temps, top_ks, top_ps,
                    keys, folds, self._plan, self._d_plan, bucket, k,
                    sampled)
        dispatch_s = self._lap(_DISPATCH) - t0
        if self.timeline:
            # enqueue cost in the dispatching iteration's ledger (the
            # fetch lands in the committing one's) — see the plain
            # pipeline's convention
            self._iter_decode_s += dispatch_s
        return _PendingSpec(drafts, n_acc, bonus,
                            tuple((s, s.request) for s in ds),
                            bucket, dispatch_s, t0)

    def _commit_spec(self, pending: Optional[_PendingSpec]) -> None:
        """Land one speculative window: ONE fused tuple transfer for
        (drafts, n_acc, bonus) — the three per-iteration host reads
        collapse into a single ``device_get`` round trip — then the
        per-slot commit. Serial mode calls this immediately after the
        dispatch; the dispatch-ahead loop calls it one iteration
        late, after the next iteration's admission/prefill work
        overlapped the window's device compute."""
        if pending is None:
            return
        ds = [slot for slot, _ in pending.riders]
        k = self.speculate_k
        bucket = pending.bucket
        t0 = self._lap(_STAGE)
        with obs.span("serve/commit_fetch"):
            # graftlint: allow[R2] the speculative window's deferred commit fetch: one fused tuple transfer per window (three reads collapsed), data-dependent acceptance makes it unavoidable
            drafts, n_acc, bonus = map(np.asarray, jax.device_get(
                (pending.drafts, pending.n_acc, pending.bonus)))
        t_end = self._lap(_FETCH)
        fetch_s = t_end - t0
        self.decode_time_s += pending.dispatch_s + fetch_s
        self.decode_steps += 1
        self.spec_windows += len(ds)
        if self.timeline:
            self._iter_decode_s += fetch_s
            self._iter_decode_slots = len(ds)
        with obs.span("serve/commit"):
            committed = []
            for slot in ds:
                req = slot.request
                i = slot.index
                acc = int(n_acc[i])
                self.draft_proposed += k
                self.draft_accepted += acc
                req.spec_proposed += k
                req.spec_accepted += acc
                if self.timeline:
                    # committed-token count lands below, one bump per
                    # append (the finish emission inside _append must see
                    # the segment current); the window's attributed
                    # interval is [dispatch, fetch-end] — the concurrent
                    # host work is decode time, not overhead — clipped
                    # against the request's previous interval (a no-op in
                    # serial mode, where commit precedes the next
                    # dispatch)
                    start = pending.t_dispatch
                    if req.decode_attr_end is not None:
                        start = max(start, req.decode_attr_end)
                    self._accrue_decode(req, start, t_end - start,
                                        bucket, 0, k, acc)
                    req.decode_attr_end = t_end
                window = [int(drafts[i, j]) for j in range(acc)]
                window.append(int(bonus[i]))
                j = 0
                for tok in window:
                    j += 1
                    slot.context_len += 1    # this token's K/V is resident
                    self.decode_tokens += 1
                    if self.timeline:
                        req.segments[-1]["tokens"] += 1
                    self._append(slot, tok)
                    if req.rid in self.finished:
                        break                # EOS / budget: drop the rest
                committed.append(j)
                if req.rid not in self.finished:
                    # rejected-tail blocks (reserved for the verify window,
                    # now holding only stale K/V) go back to the free list
                    self.blocks.trim(slot.table, slot.context_len)
        self.blocks.note_verify(committed, k + 1)
        self._lap(_COMMIT)

    # -- lifecycle tracing (ISSUE 10) ----------------------------------------
    #
    # All host-side perf_counter stamps: the decomposition the
    # `request_timeline` event carries is CHECKABLE — queue + prefill +
    # decode + preempted + overhead sums to the request's e2e (overhead
    # is the derived remainder: host scheduling, COW copies, and the
    # stall a resident request pays for dispatches it did not ride, e.g.
    # a decoding slot waiting out another request's prefill chunk).
    # Dispatch durations are attributed to EVERY request riding the
    # dispatch (they run concurrently — this is per-request latency
    # attribution, not a wall-clock partition across requests), and each
    # request's attributed intervals are disjoint in wall time, so its
    # phase sum can never exceed e2e (negative overhead = accounting
    # bug, which `obs.timeline.check_decomposition` flags).

    def _stamp_admit(self, slot, n_cow: int) -> None:
        """Close the request's queue (first admission) or preempted
        (re-admission) interval and record its segment — with the
        cached-prefix skip, admission-block attribution, and COW-copy
        count riding as extras."""
        req = slot.request
        now = time.perf_counter()
        if req.preempt_t is not None:
            phase, t_from = "preempted", req.preempt_t
        else:
            phase, t_from = "queue", req.submit_t
        dt = max(now - t_from, 0.0)
        req.phase_s[phase] += dt
        seg = {"ph": phase, "t0": t_from - req.submit_t, "dur": dt}
        if req.trace_id:
            # fleet tracing (ISSUE 19): segments carry WHERE they ran,
            # and a segment that closes a migration hold says so — the
            # stitcher splits cross-engine admission wait (`via:
            # "migrate"`, priced net of the source's extraction
            # seconds) out of same-engine preemption. Tagged only on
            # traced requests: untraced streams stay byte-identical.
            if self.replica is not None:
                seg["replica"] = self.replica
            if req.rid in self._migrate_hold:
                self._migrate_hold.discard(req.rid)
                if phase == "preempted":
                    seg["via"] = "migrate"
                    seg["hop"] = req.hop
        if slot.prefill_pos:
            # prefix-cache hit: prefill starts past the cached span
            seg["cached_tokens"] = int(slot.prefill_pos)
        if req.blocked_iters:
            seg["blocked_iters"] = req.blocked_iters
            seg["blocked_reason"] = req.blocked_reason
            req.blocked_iters = 0
        req.segments.append(seg)
        req.preempt_t = None
        req.cow_copies += n_cow

    def _accrue_prefill(self, slot, t0: float, dur: float) -> None:
        """Attribute one prefill dispatch's wall time to a riding slot;
        consecutive chunks coalesce into one segment (dur accumulates
        dispatch time only — host gaps between chunks stay overhead)."""
        req = slot.request
        req.phase_s["prefill"] += dur
        last = req.segments[-1] if req.segments else None
        if last is not None and last["ph"] == "prefill":
            last["dur"] += dur
            last["chunks"] += 1
        else:
            seg = {"ph": "prefill",
                   "t0": t0 - req.submit_t, "dur": dur,
                   "from": int(slot.prefill_pos),
                   "chunks": 1}
            if req.trace_id and self.replica is not None:
                seg["replica"] = self.replica
            req.segments.append(seg)

    def _accrue_decode(self, req: Request, t0: float, dur: float,
                       bucket: int, tokens: int, proposed: int = 0,
                       accepted: int = 0) -> None:
        """Attribute one decode dispatch to a riding request.
        Consecutive iterations at the SAME gather bucket coalesce into
        one segment run (per-iteration granularity is preserved exactly
        where it matters — a bucket switch starts a new run); a
        speculative engine's runs additionally carry the window
        acceptance counts."""
        req.phase_s["decode"] += dur
        last = req.segments[-1] if req.segments else None
        if (last is not None and last["ph"] == "decode"
                and last["bucket"] == bucket):
            last["dur"] += dur
            last["iters"] += 1
            last["tokens"] += tokens
            if self.speculative:
                last["proposed"] += proposed
                last["accepted"] += accepted
        else:
            seg = {"ph": "decode", "t0": t0 - req.submit_t, "dur": dur,
                   "bucket": int(bucket), "iters": 1, "tokens": tokens}
            if req.trace_id and self.replica is not None:
                seg["replica"] = self.replica
            if self.speculative:
                seg["proposed"] = proposed
                seg["accepted"] = accepted
            req.segments.append(seg)

    def _emit_timeline(self, req: Request, at: str,
                       now: Optional[float] = None) -> None:
        """One compact ``request_timeline`` event: the five-way phase
        decomposition plus the coalesced segment list. Emitted at
        finish (complete) and at preempt-requeue (partial, ``at`` says
        which — consumers keep the LAST event per request)."""
        if not (self.timeline and obs.has_sink()):
            return
        end = req.finish_t if at == "finish" else now
        e2e = max(end - req.submit_t, 0.0)
        q = req.phase_s["queue"]
        pf = req.phase_s["prefill"]
        dc = req.phase_s["decode"]
        pe = req.phase_s["preempted"]
        segs = [{k: (round(v, 6) if isinstance(v, float) else v)
                 for k, v in s.items()} for s in req.segments]
        fields = {
            "request": req.rid, "at": at,
            "e2e_s": round(e2e, 6),
            "queue_s": round(q, 6),
            "prefill_s": round(pf, 6),
            "decode_s": round(dc, 6),
            "preempted_s": round(pe, 6),
            "overhead_s": round(e2e - (q + pf + dc + pe), 6),
            "tokens": self._generated(req),
            "prompt_len": req.orig_prompt_len,
            "preemptions": req.preemptions,
            "segments": segs,
        }
        if req.ttft_s is not None:
            fields["ttft_s"] = round(req.ttft_s, 6)
        fields.update(self._replica_kw())
        fields.update(self._trace_kw(req))
        if req.group:
            fields["group"] = req.group
        # open-loop riders (ISSUE 16): the arrival stamp lets goodput
        # attribution join pre-submit backlog onto the phase split, and
        # the finish-time verdict lets `obsctl goodput` name the
        # dominant phase of each MISS without a second join pass —
        # absent on closed-loop / target-less requests
        if req.arrival_s is not None:
            fields["arrival_s"] = round(req.arrival_s, 6)
        if at == "finish" and req.slo_met is not None:
            fields["slo_met"] = req.slo_met
            if req.slack_s is not None:
                fields["slack_s"] = req.slack_s
        # admission-policy riders (ISSUE 20) — absent unless the
        # request actually carried a deadline / nonzero priority
        if req.deadline_s is not None:
            fields["deadline_s"] = req.deadline_s
            if at == "finish" and req.deadline_miss is not None:
                fields["deadline_miss"] = req.deadline_miss
        if req.priority:
            fields["priority"] = req.priority
        if req.cow_copies:
            fields["cow_copies"] = req.cow_copies
        if self.prefix_cache:
            fields["prefix_cached_tokens"] = req.prefix_cached_tokens
        # admission-block attribution rides the queue/preempted
        # SEGMENTS (closed by _stamp_admit) — emission here happens
        # only at finish or at the preempt instant, when the request
        # was resident and blocked_iters is necessarily 0
        obs.serve("request_timeline", **fields)

    # -- helpers -------------------------------------------------------------

    def _apply_cow(self, slot) -> None:
        """Apply the admission's queued copy-on-write block copies to
        EVERY pool addressed by the slot's table — the draft's pools
        ride the same block tables as the target's, so both KV address
        spaces must duplicate the privatized blocks."""
        for src, dst in slot.pending_copies:
            self._pools = self._copy_fn(self._pools, np.int32(src),
                                        np.int32(dst))
            if self.speculative:
                self._d_pools = self._copy_fn(self._d_pools,
                                              np.int32(src), np.int32(dst))
        slot.pending_copies = []

    def _spill_block(self, b: int):
        """BlockManager spill hook (ISSUE 17): one block's payload out
        of the live pools — target and draft atomically, int8 scale
        planes included (they are ordinary pool entries in the plan)."""
        return extract_blocks(
            self._pools, [b],
            d_pools=self._d_pools if self.speculative else None)

    def _swap_out(self, slot) -> bool:
        """Scheduler preemption hook (ISSUE 17): try to EXTRACT the
        victim's resident blocks to host instead of recomputing. Runs
        before the scheduler releases the table (extraction copies; the
        release is the same either way), and only ever on committed
        state — the overlap pipeline drained before the capacity phase
        that picked this victim, exactly as for recompute. Returns True
        when the request now carries its ``swap_set`` (the scheduler
        then skips the prompt fold), False to fall back to vLLM
        recompute: policy ``never``/``off``, an ``auto`` estimate that
        favors re-prefill, or a host budget that cannot take the
        reservation."""
        if self.swap in ("off", "never"):
            return False
        req = slot.request
        n = self.blocks.blocks_for(slot.context_len)
        if n <= 0 or n > len(slot.table):
            return False
        est = n * self._host_block_bytes
        if self.swap == "auto":
            # bytes moved (extract now + scatter on re-admit) vs the
            # weight traffic re-prefill streams: params once per chunk
            # dispatch. Contexts long enough that re-prefill re-reads
            # the weights more than the block set costs to round-trip
            # swap; short ones recompute — the vLLM crossover.
            dispatches = -(-slot.context_len // self.sched.prefill_chunk)
            if 2 * est > self._param_bytes * dispatches:
                return False
        if not self.blocks.host_reserve(est):
            return False
        req.swap_set = extract_blocks(
            self._pools, slot.table[:n],
            d_pools=self._d_pools if self.speculative else None)
        actual = req.swap_set.nbytes
        if actual != est:
            # true the reservation up to the payload's real size (the
            # estimate is exact for full pools; belt and braces)
            self.blocks.host_release(est - actual)
        req.swap_context = slot.context_len
        self.swap_outs += 1
        self.swap_bytes_moved += actual
        if obs.has_sink():
            obs.serve("swap_out", request=req.rid, swap_bytes=actual,
                      **self._replica_kw(), **self._trace_kw(req))
        return True

    def _apply_restores(self, slot) -> None:
        """Apply the admission's queued HOST->DEVICE scatters before
        any dispatch reads the slot's table (the pending-copies timing
        contract): a swapped victim's whole block set, and/or the
        per-block prefix-cache revivals the reservation pulled out of
        the host tier."""
        req = slot.request
        if slot.pending_swap_in is not None:
            bset, slot.pending_swap_in = slot.pending_swap_in, None
            t0 = time.perf_counter()
            self._pools, d = insert_blocks(
                self._pools, bset, slot.table[:bset.n_blocks],
                d_pools=self._d_pools if self.speculative else None,
                donate=self._donate)
            if self.speculative:
                self._d_pools = d
            dt = time.perf_counter() - t0
            if req.rid in self._migrated_in:
                # migration arrival (ISSUE 18): the set came from a
                # SIBLING engine's pools, not this engine's host tier —
                # no reservation to release (host_release here would
                # corrupt the swap budget), and the traffic lands in
                # migration accounting, not the swap tier's
                src_replica = self._migrated_in.pop(req.rid)
                self.migrations_in += 1
                self.migration_bytes += bset.nbytes
                self.migration_restore_s += dt
                kw = {}
                if src_replica is not None:
                    kw["from_replica"] = src_replica
                if self.replica is not None:
                    kw["to_replica"] = self.replica
                kw.update(self._trace_kw(req))
                if req.trace_id and req.migrate_out_t is not None:
                    # the transport hop's full price (ISSUE 19):
                    # source extraction stamp → destination scatter
                    # complete — the sample behind the router's
                    # transport_hop_s_p99 rider; extract_s rides so
                    # the stitcher can split pure data movement out
                    # of the admission wait it telescopes against
                    kw["transport_hop_s"] = round(
                        time.perf_counter() - req.migrate_out_t, 6)
                    kw["extract_s"] = round(req.migrate_extract_s, 6)
                    self.transport_hop_s.append(kw["transport_hop_s"])
                req.migrate_out_t = None
                req.migrate_extract_s = 0.0
                obs.serve("migrate", request=req.rid,
                          migration_bytes=bset.nbytes,
                          restore_s=round(dt, 6), **kw)
            else:
                self.restore_s += dt
                self.blocks.host_release(bset.nbytes)
                self.swap_ins += 1
                self.swap_bytes_moved += bset.nbytes
                self.recompute_tokens_avoided += slot.context_len
                if obs.has_sink():
                    obs.serve("swap_in", request=req.rid,
                              swap_bytes=bset.nbytes,
                              restore_s=round(dt, 6),
                              recompute_tokens_avoided=slot.context_len,
                              **self._replica_kw(), **self._trace_kw(req))
        if slot.pending_restores:
            t0 = time.perf_counter()
            for b, payload in slot.pending_restores:
                self._pools, d = insert_blocks(
                    self._pools, payload, [b],
                    d_pools=self._d_pools if self.speculative else None,
                    donate=self._donate)
                if self.speculative:
                    self._d_pools = d
            self.restore_s += time.perf_counter() - t0
            slot.pending_restores = []

    def _generated(self, req: Request) -> int:
        return (len(req.prompt) - req.orig_prompt_len) + len(req.output)

    def _append(self, slot, token: int) -> None:
        req = slot.request
        req.output.append(token)
        now = time.perf_counter()
        if req.first_token_t is None:
            req.first_token_t = now
            if obs.has_sink():
                obs.serve("first_token", request=req.rid,
                          ttft_s=round(req.ttft_s, 6)
                          if req.ttft_s is not None else None,
                          **self._replica_kw(), **self._trace_kw(req))
        self.tokens_generated += 1
        if (token == self.eos_token_id
                or self._generated(req) >= req.max_new_tokens):
            req.finish_t = now
            self.sched.finish(slot)
            self.finished[req.rid] = req
            self._keys.pop(req.rid, None)
            # the verdicts are written on the request, sink or no sink
            verdicts = {}
            if req.has_slo:
                verdicts.update(self._slo_verdict(req))
            if req.deadline_s is not None:
                verdicts.update(self._deadline_verdict(req))
            if obs.has_sink():
                extra = {}
                if self.speculative:
                    extra = {
                        "speculate_k": self.speculate_k,
                        "draft_proposed": req.spec_proposed,
                        "draft_accepted": req.spec_accepted,
                        "acceptance_rate": (
                            round(req.spec_accepted / req.spec_proposed, 4)
                            if req.spec_proposed else None),
                    }
                if self.prefix_cache:
                    extra["prefix_cached_tokens"] = \
                        req.prefix_cached_tokens
                    extra["cache_hit_rate"] = (
                        round(req.cache_hit_rate, 4)
                        if req.cache_hit_rate is not None else None)
                extra["kernel"] = self.kernel
                extra["kv_dtype"] = self.kv_cache_dtype
                extra["tp"] = self.tp
                obs.serve("finish", request=req.rid,
                          tokens=self._generated(req),
                          preemptions=req.preemptions,
                          **self._replica_kw(), **self._trace_kw(req),
                          **extra, **verdicts)
            self._emit_timeline(req, "finish")

    def _slo_verdict(self, req: Request) -> dict:
        """Write the request's SLO verdicts at finish and return the
        finish-event riders (ISSUE 16). TTFT is measured from the
        ARRIVAL stamp when one was threaded (the open-loop truth — the
        request waited from arrival, not from when the generator got
        around to submitting it), else from the submit stamp. TPOT is
        the steady-state inter-token mean over the post-first-token
        tail. ``slack_s`` is the TIGHTEST remaining margin across the
        set targets — negative exactly on a miss, the quantity a
        capacity planner reads as "how close to the knee"."""
        origin = (req.arrival_s if req.arrival_s is not None
                  else req.submit_t)
        margins = []
        if req.slo_ttft_s is not None:
            ttft = ((req.first_token_t - origin)
                    if req.first_token_t is not None else None)
            req.ttft_slo_met = (ttft is not None
                                and ttft <= req.slo_ttft_s)
            if ttft is not None:
                margins.append(req.slo_ttft_s - ttft)
        if req.slo_tpot_s is not None:
            tokens = self._generated(req)
            tpot = ((req.finish_t - req.first_token_t)
                    / max(tokens - 1, 1)
                    if req.first_token_t is not None else None)
            req.tpot_slo_met = (tpot is not None
                                and tpot <= req.slo_tpot_s)
            if tpot is not None:
                margins.append(req.slo_tpot_s - tpot)
        req.slo_met = (req.ttft_slo_met is not False
                       and req.tpot_slo_met is not False)
        if margins:
            req.slack_s = round(min(margins), 6)
        self._slo_total += 1
        self._slo_met += int(req.slo_met)
        bucket = self._group_slo.setdefault(req.group, [0, 0])
        bucket[0] += int(req.slo_met)
        bucket[1] += 1
        if self._has_priorities:
            # per-priority-class attainment (ISSUE 20): only tracked
            # once any submit named a class, so the rider — and this
            # dict — stays absent on priority-less traffic
            pb = self._priority_slo.setdefault(req.priority, [0, 0])
            pb[0] += int(req.slo_met)
            pb[1] += 1
        out = {"slo_met": req.slo_met}
        if req.ttft_slo_met is not None:
            out["ttft_slo_met"] = req.ttft_slo_met
        if req.tpot_slo_met is not None:
            out["tpot_slo_met"] = req.tpot_slo_met
        if req.slack_s is not None:
            out["slack_s"] = req.slack_s
        return out

    def _deadline_verdict(self, req: Request) -> dict:
        """End-to-end deadline verdict at finish (ISSUE 20): measured
        from the same origin as the SLO verdicts (arrival when
        threaded, else submit), so deadline slack and TTFT share one
        time domain. Feeds ``deadline_miss_frac`` — the figure the
        slo admission policy exists to push down — and the
        ``deadline_miss`` riders on the finish/timeline events."""
        origin = (req.arrival_s if req.arrival_s is not None
                  else req.submit_t)
        req.deadline_miss = bool(
            req.finish_t - origin > req.deadline_s)
        self._deadline_total += 1
        self._deadline_miss += int(req.deadline_miss)
        return {"deadline_miss": req.deadline_miss}
