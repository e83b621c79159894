"""Multi-replica serving router (ISSUE 14): N :class:`~.engine.
ServeEngine` replicas — each with its own scheduler, BlockManager,
prefix cache, and telemetry stream — behind ONE ``submit()``/``run()``
facade, with pluggable SLO- and prefix-affinity-aware placement.

This is the data-parallel remainder of the scale-out story: PR 13 made
one engine span chips (tensor parallel — a model bigger than a chip);
the router spreads *requests* over N such engines (traffic bigger than
an engine). vLLM-style fleets win most of their throughput at the
replica-level load balancer, and Sarathi-Serve's analysis says tail
latency is won or lost at placement/admission time — and the repo
already emits every signal a smart router needs (the scheduler's live
waiting-depth/KV-pressure gauges, PR 10's queue-wait attribution, the
PR 7 prefix fingerprints), so the router wires them into a placement
policy instead of FIFO-into-one-engine:

- ``round_robin`` — cycle over admitting replicas; the trivially fair
  baseline every policy gate compares against.
- ``least_loaded`` — score each replica by
  ``waiting_depth + occupied_slots + kv_used_frac`` (the engine's own
  live :meth:`~.engine.ServeEngine.load_gauges`, read host-side — the
  router never parses its own telemetry to route) and place on the
  argmin, index-tiebroken so placement is deterministic.
- ``affinity`` — a ROUTER-level prefix-fingerprint index built from
  the same chain-key hashing as the BlockManager's block-level prefix
  cache (:func:`~.paged_kv.prefix_chain_keys`: key N commits to the
  whole token prefix through chunk N): a request routes to the replica
  whose index entry covers its LONGEST hashed prefix — the replica
  most likely to hold its KV blocks warm — so templated families stick
  to a replica and the per-replica prefix caches stay hot instead of
  every replica paying every family's cold miss. The index is a pure
  function of tokens (no block ids), LRU-aged to ``affinity_cap``
  entries, and IMBALANCE-BOUNDED: when the sticky replica is more than
  ``affinity_max_skew`` load units deeper than the lightest sibling
  (default: one full slot batch), the request falls back to
  least-loaded — affinity is a cache heuristic and must never starve
  load balance (the cache-aware admission-ordering follow-up of PR 7,
  generalized across replicas). Any placement is CORRECT: every
  replica produces token-identical output (greedy exact, sampled
  bitwise — per-request seeds), so a stale or evicted index entry
  degrades to a cold cache, never to wrong tokens.

Replica drain/restart — the fleet degrades instead of dying:
:meth:`Router.drain` stops admitting to replica i, requeues its
WAITING requests onto siblings through the normal placement policy
(recompute semantics, the same state the scheduler's preemption
/requeue path builds — a preemption-folded prompt moves unchanged,
sampled keys re-derive from the request's own seed, queue-wait keeps
counting from the original submit stamp), and LIVE-MIGRATES its
RESIDENT requests (ISSUE 18): each resident's KV block set moves to a
sibling through :func:`~.transport.migrate_request` with zero
re-prefill, so a drain completes without waiting for any resident to
finish — preemption-free rolling restarts. A resident no sibling can
take (heterogeneous fleets) finishes in place, counted in the drain
event's ``residents_in_place``. :meth:`Router.restart` re-admits.
Every move is telemetered (``drain`` / ``requeue`` / ``migrate`` /
``restart`` serve events).

Disaggregated fleets (ISSUE 18): ``Router(roles="prefill:N,decode:M")``
designates prefill-only and decode-only replicas. Submissions place
over the prefill side only; a prefill replica runs chunked prefill
with its decode phase suppressed entirely (its idle decode slots feed
the Sarathi token budget, so prefill runs at full width instead of
one chunk per iteration), and each finished prefill's block set is
handed to the least-loaded decode replica between fleet iterations —
wide prefill dispatches never stall another tenant's decode iteration,
which is the DistServe/Splitwise goodput argument the bench's
disaggregation line gates. With ``replica_kwargs`` the fleet may also
be HETEROGENEOUS (e.g. TP=2 replicas for long-context traffic beside
TP=1 for short) — the ``length_aware`` placement policy routes by
prompt length, and migration re-shards the KV heads axis simply by
scattering into the destination's own sharded pools.

Telemetry: each engine's per-request lifecycle events carry a
``replica`` tag (``obsctl slo`` groups tail attribution by it); the
router's ``run()`` emits one report event per replica plus ONE
aggregate report last (``placement``, ``replicas``,
``replica_load_imbalance`` = max/mean requests served — the figure
``obsctl diff`` watches — and a ``per_replica`` hit-rate/depth
breakdown). A ``replicas=1`` router is a pass-through: it drives the
single engine's own ``run()`` and tags nothing, so its telemetry is
byte-identical to the pre-router engine stream (allowlist-gated).

Compile expectations: replicas over the same model/geometry share the
module-level jitted step families (static keys are (model, plan,
bucket, sampled) — identical across replicas), so N replicas compile
ONE bucket ladder total, not N.
"""

from __future__ import annotations

import contextlib
import os
import time
import types
from collections import OrderedDict
from typing import Optional, Union

import numpy as np

from huggingface_sagemaker_tensorflow_distributed_tpu import obs
from huggingface_sagemaker_tensorflow_distributed_tpu.serve.engine import (
    ServeEngine,
)
from huggingface_sagemaker_tensorflow_distributed_tpu.serve.paged_kv import (
    extract_block_sets,
    prefix_chain_keys,
)
from huggingface_sagemaker_tensorflow_distributed_tpu.serve.policy import (
    RateLimited,
    TokenBucket,
    parse_aging_s,
    parse_policy,
    parse_rate_limit,
)
from huggingface_sagemaker_tensorflow_distributed_tpu.serve.scheduler import (
    DECODE,
    Request,
)
from huggingface_sagemaker_tensorflow_distributed_tpu.serve.transport import (
    TransportError,
    can_accept,
    migrate_request,
)

ENV_REPLICAS = "HSTD_SERVE_REPLICAS"
ENV_PLACEMENT = "HSTD_SERVE_PLACEMENT"
ENV_ROLES = "HSTD_SERVE_ROLES"
ENV_TRACE = "HSTD_SERVE_TRACE"

PLACEMENTS = ("round_robin", "least_loaded", "affinity", "length_aware")


def parse_replicas(spec) -> int:
    """The replica-count knob: a positive int. None reads
    ``HSTD_SERVE_REPLICAS`` (default 1 = the single pass-through
    engine, byte-identical telemetry)."""
    if spec is None:
        spec = os.environ.get(ENV_REPLICAS, "1") or "1"
    try:
        n = int(str(spec).strip() or "1")
    except ValueError:
        raise ValueError(f"unparseable {ENV_REPLICAS} value {spec!r}: "
                         "expected a positive integer")
    if n < 1:
        raise ValueError(f"{ENV_REPLICAS} must be >= 1, got {n}")
    return n


def parse_roles(spec) -> Optional[dict]:
    """The disaggregation knob (ISSUE 18): ``prefill:N,decode:M``
    (both >= 1) designates the first N replicas prefill-only and the
    next M decode-only; an empty value keeps every replica mixed (the
    pre-disaggregation fleet, byte-identical behavior). None reads
    ``HSTD_SERVE_ROLES``. A dict ``{"prefill": N, "decode": M}``
    passes through."""
    if spec is None:
        spec = os.environ.get(ENV_ROLES, "")
    if isinstance(spec, dict):
        parts = {str(k).strip().lower(): v for k, v in spec.items()}
    else:
        s = str(spec).strip().lower()
        if not s:
            return None
        parts = {}
        for tok in s.split(","):
            role, sep, count = tok.partition(":")
            if not sep:
                raise ValueError(
                    f"unparseable {ENV_ROLES} value {spec!r}: expected "
                    "role:count pairs like 'prefill:1,decode:1'")
            parts[role.strip()] = count.strip()
    unknown = set(parts) - {"prefill", "decode"}
    if unknown:
        raise ValueError(
            f"unparseable {ENV_ROLES} value {spec!r}: unknown role(s) "
            f"{sorted(unknown)} (expected prefill / decode)")
    try:
        out = {"prefill": int(parts.get("prefill", 0)),
               "decode": int(parts.get("decode", 0))}
    except (TypeError, ValueError):
        raise ValueError(
            f"unparseable {ENV_ROLES} value {spec!r}: counts must be "
            "positive integers")
    if out["prefill"] < 1 or out["decode"] < 1:
        raise ValueError(
            f"{ENV_ROLES} needs at least one prefill and one decode "
            f"replica, got {out}")
    return out


def parse_placement(spec: Union[str, None]) -> str:
    """The placement-policy knob: one of ``round_robin`` (default) /
    ``least_loaded`` / ``affinity`` / ``length_aware``. None reads
    ``HSTD_SERVE_PLACEMENT``."""
    if spec is None:
        spec = os.environ.get(ENV_PLACEMENT, "round_robin")
    s = str(spec).strip().lower() or "round_robin"
    if s not in PLACEMENTS:
        raise ValueError(f"unparseable {ENV_PLACEMENT} value {spec!r}: "
                         f"expected {' | '.join(PLACEMENTS)}")
    return s


def parse_trace(spec) -> bool:
    """The fleet-tracing knob (ISSUE 19): ``on`` (default) mints a
    ``trace_id`` + hop counter per MULTI-replica submit so every
    lifecycle event the request leaves — on whichever engine — can be
    stitched back into one causal trace (:mod:`~.obs.trace`); ``off``
    suppresses minting, telemetry byte-identical to the pre-tracing
    stream. None reads ``HSTD_SERVE_TRACE``. Single-replica routers
    never mint regardless (the pass-through byte-identity contract —
    there is nothing to stitch)."""
    if spec is None:
        spec = os.environ.get(ENV_TRACE, "on")
    s = str(spec).strip().lower() or "on"
    if s not in ("on", "off"):
        raise ValueError(f"unparseable {ENV_TRACE} value {spec!r}: "
                         "expected on | off")
    return s == "on"


class Router:
    """N :class:`~.engine.ServeEngine` replicas behind one facade.
    ``replicas``/``placement``/``roles`` read their env knobs when
    None (``HSTD_SERVE_REPLICAS`` / ``HSTD_SERVE_PLACEMENT`` /
    ``HSTD_SERVE_ROLES``); every other keyword is forwarded verbatim
    to EACH replica's engine constructor — homogeneous by default
    (which is what makes a drain-requeued request's submit-time
    validation transferable), with per-replica ``replica_kwargs``
    overrides for heterogeneous fleets (ISSUE 18: transport re-checks
    geometry before every cross-replica move).

    ``affinity_cap`` bounds the affinity index (LRU aging — oldest
    fingerprints fall out first, exactly the staleness order the
    per-replica block caches evict in). ``affinity_max_skew`` is the
    load-imbalance bound past which an affinity hit is overridden by
    least-loaded placement (default: one engine's ``num_slots`` — a
    full batch of queue depth buys back a cold prefill, not more).

    Placement changes WHERE a request runs, never WHAT it emits:
    per-request output is token-identical to a single-engine run under
    every policy and across drains (greedy exact, sampled bitwise —
    the engine's own exactness/seed contracts, which are per-request
    and placement-blind)."""

    def __init__(self, model, params, *, replicas=None, placement=None,
                 roles=None, replica_kwargs=None,
                 length_threshold: Optional[int] = None,
                 affinity_cap: int = 4096,
                 affinity_max_skew: Optional[int] = None,
                 trace=None, policy=None, aging_s=None,
                 rate_limit=None, **engine_kwargs):
        self.roles = parse_roles(roles)
        # admission policy (ISSUE 20): parsed ONCE here and threaded
        # into every replica's engine, so one env read configures the
        # whole fleet identically (a replica_kwargs override can still
        # diverge a replica deliberately)
        self.policy = parse_policy(policy)
        self.aging_s = parse_aging_s(aging_s)
        if self.roles is not None:
            n_roles = self.roles["prefill"] + self.roles["decode"]
            if replicas is not None and parse_replicas(replicas) != n_roles:
                raise ValueError(
                    f"replicas={replicas} contradicts roles {self.roles} "
                    f"(= {n_roles} replicas): pass one or the other")
            self.n = n_roles
        else:
            self.n = parse_replicas(replicas)
        self.placement = parse_placement(placement)
        # per-replica overrides (ISSUE 18, heterogeneous fleets): the
        # shared engine_kwargs build the fleet's common geometry; a
        # replica_kwargs[i] dict layers replica i's own knobs (e.g.
        # mesh=2 for a TP=2 long-context replica) on top. Transportable
        # requests require equal POOL signatures (transport validates),
        # which mixed-TP replicas over one model satisfy by design.
        if replica_kwargs is not None and len(replica_kwargs) != self.n:
            raise ValueError(
                f"replica_kwargs has {len(replica_kwargs)} entries for "
                f"{self.n} replicas")
        self.engines = []
        for i in range(self.n):
            kw = dict(engine_kwargs, policy=self.policy,
                      aging_s=self.aging_s)
            if replica_kwargs is not None:
                kw.update(replica_kwargs[i])
            self.engines.append(ServeEngine(model, params, **kw))
        if self.n > 1:
            for i, eng in enumerate(self.engines):
                eng.replica = i
        self.role_of: list[str] = (
            ["prefill"] * self.roles["prefill"]
            + ["decode"] * self.roles["decode"]
            if self.roles is not None else ["mixed"] * self.n)
        for i, eng in enumerate(self.engines):
            if self.role_of[i] == "prefill":
                eng.prefill_only = True
        self.block_size = self.engines[0].blocks.block_size
        self._rr = 0
        self._draining: set[int] = set()
        self._owner: dict[int, int] = {}        # rid -> replica index
        self.drains = 0
        self.requeues = 0
        self.migrations = 0
        # fleet tracing (ISSUE 19): mint only on real fleets — a
        # single-replica router is the byte-identical pass-through and
        # mints nothing. The id is deterministic (router-scoped
        # sequence), so replayed runs produce identical traces.
        self.trace = parse_trace(trace) and self.n > 1
        self._trace_seq = 0
        # length-aware routing threshold (heterogeneous fleets):
        # prompts at/above it go to the deepest capacity class
        if length_threshold is None:
            length_threshold = min(
                e.sched.max_model_len for e in self.engines) // 2
        self.length_threshold = int(length_threshold)
        self.affinity_cap = int(affinity_cap)
        if self.affinity_cap < 1:
            raise ValueError("affinity_cap must be >= 1")
        if affinity_max_skew is None:
            affinity_max_skew = self.engines[0].num_slots
        self.affinity_max_skew = float(affinity_max_skew)
        self.affinity_fallbacks = 0
        # chain key -> replica index, newest-used last (LRU aging)
        self._affinity: "OrderedDict[int, int]" = OrderedDict()
        # per-tenant token buckets (ISSUE 20), keyed on `group`: a
        # submit past its bucket returns a structured RateLimited
        # rejection — never a silent drop. The `*` entry is the
        # default bucket for groups without their own; no spec = no
        # rate limiting (byte-identical submit path).
        self._rate_spec = parse_rate_limit(rate_limit)
        self._buckets: dict[str, TokenBucket] = {}
        self.rate_limited = 0

    # -- placement -----------------------------------------------------------

    def _admitting(self) -> list[int]:
        return [i for i in range(self.n) if i not in self._draining]

    def _intake(self) -> list[int]:
        """Replicas NEW submissions may target: every admitting one —
        minus the decode side of a disaggregated fleet, which only
        receives migrated residents (ISSUE 18)."""
        cand = self._admitting()
        if self.roles is not None:
            cand = [i for i in cand if self.role_of[i] == "prefill"]
        return cand

    def _load(self, i: int) -> float:
        """One replica's placement score from its live gauges: queued +
        resident requests (each is one unit of service ahead of a new
        arrival) plus the KV pool pressure fraction (breaks ties
        toward the replica with block headroom — the one least likely
        to preempt what it admits)."""
        g = self.engines[i].load_gauges()
        return g["waiting_depth"] + g["running"] + g["kv_used_frac"]

    def _least_loaded(self, cand: list[int]) -> int:
        return min(cand, key=lambda i: (self._load(i), i))

    def _affine(self, prompt, cand: list[int]) -> int:
        """The replica covering the prompt's longest hashed prefix —
        unless it is draining or past the imbalance bound, in which
        case fall back to least-loaded (counted, so the bench can see
        affinity yielding to load balance rather than starving it)."""
        hit: Optional[int] = None
        for key, _chunk in prefix_chain_keys(prompt, self.block_size):
            rep = self._affinity.get(key)
            if rep is None:
                break
            hit = rep                    # deepest indexed level wins
        if hit is None:
            return self._least_loaded(cand)
        if hit not in cand or (self._load(hit)
                               - min(self._load(i) for i in cand)
                               > self.affinity_max_skew):
            self.affinity_fallbacks += 1
            return self._least_loaded(cand)
        return hit

    def _register_affinity(self, prompt, replica: int) -> None:
        """Point every full-chunk fingerprint of ``prompt`` at the
        replica that will prefill (and therefore block-cache) it;
        last-writer-wins on requeue redirects, LRU-aged at
        ``affinity_cap``. The index is a routing heuristic over the
        same chain values the replica's BlockManager indexes — an
        entry outliving the physical blocks just degrades to a cold
        cache on arrival, never to wrong tokens."""
        for key, _chunk in prefix_chain_keys(prompt, self.block_size):
            if key in self._affinity:
                self._affinity.move_to_end(key)
            self._affinity[key] = replica
        while len(self._affinity) > self.affinity_cap:
            self._affinity.popitem(last=False)

    def _place(self, prompt, max_new_tokens: int = 1) -> int:
        """The policy's CHOICE only — no state moves here. Callers
        commit via :meth:`_commit_place` once the engine has accepted
        the request: a submit the scheduler rejects (over-length, can
        never fit the pool) must not advance the round-robin cursor or
        pollute the affinity index with fingerprints pointing at a
        replica that will never prefill them.

        Under ``policy="slo"`` (ISSUE 20) the default rotation is
        replaced by live ``load_gauges()`` backpressure — the same
        waiting-depth/KV-pressure signal the admission key consumes,
        so cross-replica placement and per-replica admission pull in
        the same direction. An EXPLICIT placement choice
        (least_loaded / affinity / length_aware) keeps its own
        semantics — they are already load- or cache-aware."""
        cand = self._intake()
        if len(cand) == 1:
            return cand[0]
        if self.placement == "round_robin":
            if self.policy != "fifo":
                return self._least_loaded(cand)
            return cand[self._rr % len(cand)]
        if self.placement == "least_loaded":
            return self._least_loaded(cand)
        if self.placement == "length_aware":
            return self._length_aware(prompt, cand, max_new_tokens)
        return self._affine(prompt, cand)

    def _capacity_class(self, i: int) -> tuple:
        """A replica's capacity rank for length-aware routing: its
        tensor-parallel degree first (a TP=2 replica holds the deep
        pool long contexts need), pool blocks as the tiebreak."""
        eng = self.engines[i]
        return (eng.tp, eng.blocks.num_blocks)

    def _length_aware(self, prompt, cand: list[int],
                      max_new_tokens: int = 1) -> int:
        """Heterogeneous-fleet policy (ISSUE 18): prompts at/above
        ``length_threshold`` go to the DEEPEST capacity class (TP
        degree, then pool size), short ones to the shallowest — so
        long-context traffic lands on the replicas built for it and
        never crowds the small replicas' pools. Least-loaded inside
        the chosen class; on a homogeneous fleet every replica is one
        class and this IS least-loaded.

        Admission-aware refinement (ISSUE 20, PR 18 follow-up): the
        class preference folds in LIVE pool headroom via the
        ``can_accept(live=True)`` probe — a destination whose pool
        cannot carry the request's worst case RIGHT NOW is skipped
        for a class peer with room, and when the whole preferred
        class is full the request falls out to ANY candidate with
        room rather than queueing on a full pool. Static length
        preference alone would happily stack long prompts onto a
        full deep replica while a shallow one idled."""
        shim = types.SimpleNamespace(
            prompt=np.asarray(prompt, np.int32).reshape(-1),
            max_new_tokens=int(max_new_tokens))
        classes = {self._capacity_class(i) for i in cand}
        want = max(classes) if len(prompt) >= self.length_threshold \
            else min(classes)
        pool = [i for i in cand if self._capacity_class(i) == want]
        roomy = [i for i in pool
                 if can_accept(self.engines[i], shim, live=True)]
        if not roomy:
            roomy = [i for i in cand
                     if can_accept(self.engines[i], shim, live=True)]
        return self._least_loaded(roomy or pool)

    def _commit_place(self, prompt, choice: int) -> None:
        """Land the placement's state changes for an ACCEPTED request:
        advance the round-robin rotation (only when there was a real
        choice to rotate over), register the prompt's fingerprints at
        the chosen replica."""
        if self.placement == "round_robin":
            if len(self._intake()) > 1:
                self._rr += 1
        elif self.placement == "affinity":
            self._register_affinity(prompt, choice)

    # -- public API ----------------------------------------------------------

    def submit(self, prompt, max_new_tokens: int, **kw):
        """Place one request per the policy and queue it on the chosen
        replica. Same signature/semantics as
        :meth:`~.engine.ServeEngine.submit` — the returned
        :class:`Request` is the engine's own handle.

        With per-tenant rate limits configured (ISSUE 20), a submit
        whose ``group`` bucket is empty returns a structured
        :class:`~.serve.policy.RateLimited` object instead of a
        Request — a STRUCTURAL rejection (``rate_limited`` serve
        event, counted, ``retry_after_s`` named), never a silent
        drop. The bucket clock is the caller's ``arrival_s`` when
        threaded (deterministic under the virtual-clock driver), else
        wall."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        limited = self._rate_check(str(kw.get("group", "")),
                                   kw.get("arrival_s"))
        if limited is not None:
            return limited
        if self.roles is not None:
            # the prefill side validates against ITS pool below; also
            # require that SOME decode replica can eventually hold the
            # request, or the post-prefill handoff would retry forever
            # (only reachable on heterogeneous decode sides)
            shim = types.SimpleNamespace(
                prompt=prompt, max_new_tokens=int(max_new_tokens))
            if not any(can_accept(self.engines[j], shim)
                       for j in range(self.n)
                       if self.role_of[j] == "decode"):
                raise ValueError(
                    f"request (prompt {len(prompt)} + max_new_tokens "
                    f"{max_new_tokens}) can never fit any decode "
                    "replica of the disaggregated fleet")
        i = self._place(prompt, int(max_new_tokens))
        if self.trace and "trace_id" not in kw:
            kw = dict(kw, trace_id=f"t{self._trace_seq:06d}")
            self._trace_seq += 1
        req = self.engines[i].submit(prompt, max_new_tokens, **kw)
        self._commit_place(prompt, i)       # only an ACCEPTED submit
        self._owner[req.rid] = i
        return req

    def _rate_check(self, group: str,
                    arrival_s: Optional[float]) -> Optional[RateLimited]:
        """One token-bucket decision for ``group`` (its own entry, else
        the ``*`` default, else unlimited). Buckets materialize lazily
        per group so two tenants sharing the ``*`` spec still meter
        independently — a per-tenant limit, not a global one."""
        if not self._rate_spec:
            return None
        spec = self._rate_spec.get(group, self._rate_spec.get("*"))
        if spec is None:
            return None
        bucket = self._buckets.get(group)
        if bucket is None:
            bucket = self._buckets[group] = TokenBucket(*spec)
        now = (time.perf_counter() if arrival_s is None
               else float(arrival_s))
        ok, retry_after = bucket.try_take(now)
        if ok:
            return None
        self.rate_limited += 1
        limited = RateLimited(group=group,
                              retry_after_s=round(retry_after, 6),
                              rate=spec[0], burst=spec[1])
        obs.serve("rate_limited", group=group,
                  retry_after_s=limited.retry_after_s,
                  rate_limited=self.rate_limited)
        return limited

    def replica_of(self, req: Union[Request, int]) -> int:
        """Which replica currently owns a request (post-drain requeues
        included)."""
        rid = req.rid if isinstance(req, Request) else int(req)
        return self._owner[rid]

    def output_ids(self, req: Request) -> np.ndarray:
        return self.engines[self._owner[req.rid]].output_ids(req)

    @property
    def finished(self) -> dict[int, Request]:
        """Merged {rid: Request} across replicas (rids are process
        -global, so keys never collide)."""
        out: dict[int, Request] = {}
        for eng in self.engines:
            out.update(eng.finished)
        return out

    def has_work(self) -> bool:
        return any(eng.has_work() for eng in self.engines)

    def warmup(self, sampled: bool = False) -> None:
        """Warm every replica. Replicas share the module-level jitted
        step families (identical static keys), so replica 0 compiles
        the ladder and the rest reuse it — N replicas cost one bucket
        ladder of compiles, not N."""
        for eng in self.engines:
            eng.warmup(sampled=sampled)

    def step(self) -> None:
        """One interleaved fleet iteration: each replica with work runs
        one engine iteration. With the engines' dispatch-ahead loop on
        (the default) replica A's device step stays in flight while
        replicas B..N run their whole host side — the router's
        interleave extends the PR 12 overlap across the fleet."""
        for eng in self.engines:
            if eng.has_work():
                eng.step()
        if self.roles is not None:
            self._harvest()

    def _harvest(self) -> None:
        """Disaggregated handoff (ISSUE 18): every request that
        FINISHED PREFILL on a prefill replica this iteration (parked
        in DECODE state — the replica's decode phase is suppressed)
        migrates to the least-loaded admitting decode replica with
        zero re-prefill. A saturated or draining decode side just
        defers the handoff to the next fleet iteration — the parked
        residents are the disaggregation backpressure, and their held
        slots throttle the prefill side's own admission."""
        for i, eng in enumerate(self.engines):
            if self.role_of[i] != "prefill":
                continue
            ready = sorted(
                (s for s in eng.sched.slots
                 if s.request is not None and s.request.state == DECODE),
                key=lambda s: s.admit_seq, reverse=True)
            for slot in ready:
                req = slot.request
                cand = [j for j in self._admitting()
                        if self.role_of[j] == "decode"
                        and can_accept(self.engines[j], req)]
                if not cand:
                    return
                j = self._least_loaded(cand)
                info = migrate_request(eng, self.engines[j], req.rid)
                if info is None:
                    continue        # finished at the handoff commit
                self._owner[req.rid] = j
                self.migrations += 1

    def drain(self, i: int) -> list[Request]:
        """Stop admitting to replica i: its WAITING requests requeue
        to siblings through the normal placement policy (recompute
        semantics — identical tokens, queue clock unreset), its
        RESIDENT requests LIVE-MIGRATE to the least-loaded compatible
        sibling (:func:`~.transport.migrate_request` — the KV block
        set moves, decode resumes with zero re-prefill, so the drain
        completes without waiting for any resident to finish), and
        until :meth:`restart` no new placement chooses it. A resident
        no sibling can take (heterogeneous fleets) finishes in place —
        the drain event's ``residents_in_place`` counts them. Returns
        the requeued WAITING requests (the migrated residents keep
        their engine handles; :meth:`replica_of` tracks both). Refuses
        to drain the last admitting replica — per role on a
        disaggregated fleet — a fleet with nowhere to admit is an
        outage, not a drain."""
        if not 0 <= i < self.n:
            raise ValueError(f"replica {i} out of range [0, {self.n})")
        if i in self._draining:
            raise ValueError(f"replica {i} is already draining")
        peers_like_i = [j for j in self._admitting()
                        if j != i and self.role_of[j] == self.role_of[i]]
        if not peers_like_i:
            role = ("" if self.roles is None
                    else f" {self.role_of[i]}-role")
            raise ValueError(
                f"cannot drain the last admitting{role} replica: "
                "restart a sibling first (a fleet must always have "
                "somewhere to place work)")
        self._draining.add(i)
        self.drains += 1
        src = self.engines[i]
        moved = src.take_waiting()
        for req in moved:
            if req.swap_set is not None:
                # a swap-preempted victim changing engines: return the
                # SOURCE's host-tier reservation (the destination
                # never reserved for it), and land the restore as a
                # MIGRATION arrival — its restore traffic is migration
                # traffic, not the destination's swap-tier traffic
                src.blocks.host_release(req.swap_set.nbytes)
                cand = [j for j in self._drain_peers(i, req)
                        if can_accept(self.engines[j], req)]
                if cand:
                    j = self._least_loaded(cand)
                    src.migrations_out += 1
                    self.engines[j]._migrated_in[req.rid] = i
                    self.engines[j].adopt(req)
                else:
                    # no compatible sibling for the payload: forfeit
                    # it — recompute semantics, the swap tier's own
                    # lossless fallback
                    req.swap_set = None
                    req.swap_context = 0
                    j = self._place(req.prompt, req.max_new_tokens)
                    self.engines[j].adopt(req)
                    self._commit_place(req.prompt, j)
            else:
                j = self._place(req.prompt, req.max_new_tokens)
                self.engines[j].adopt(req)      # never rejects
                self._commit_place(req.prompt, j)
            self._owner[req.rid] = j
            self.requeues += 1
            trace_kw = {}
            if req.trace_id:
                # a requeue is an inter-engine move: it advances the
                # hop counter just as migrate_request does, and the
                # event is the stitcher's evidence for that hop
                req.hop += 1
                trace_kw = {"trace_id": req.trace_id, "hop": req.hop}
            obs.serve("requeue", request=req.rid, replica=i,
                      to_replica=j, **trace_kw)
        migrated = 0
        residents_in_place = 0
        # land src's in-flight pipeline ONCE for the whole cohort
        # (each migrate_request's own flush then finds it empty): the
        # COMMITTED state decides who is hot, and the batched payloads
        # below must match the exact post-commit context lengths
        with src._mesh_ctx():
            if src._pending is not None:
                src._flush("migrate")
            if src._pending_spec is not None:
                pending, src._pending_spec = src._pending_spec, None
                src._commit_spec(pending)
        # snapshot rids: migrating one resident lands the engine's
        # in-flight pipeline, which can FINISH (or clear) others
        resident_rids = [
            s.request.rid for s in sorted(
                (s for s in src.sched.slots if s.request is not None),
                key=lambda s: s.admit_seq, reverse=True)]
        # batched cohort extraction (ISSUE 20, PR 18 follow-up (c)):
        # every hot (DECODE) victim with a compatible peer gathers its
        # block set device-side, then ONE device_get pulls the whole
        # cohort to host — V victims cost one blocking round-trip, not
        # V sequential pulls. Extraction seconds amortize evenly over
        # the cohort so each request's migrate_extract_s rider keeps
        # its transport-hop-pricing meaning. Migration count, peer
        # choice, and tokens are identical to the sequential path —
        # migrate_request falls back to its own extraction whenever a
        # prefetched set no longer matches.
        prefetched: dict[int, object] = {}
        share = 0.0
        hot = [s for s in src.sched.slots
               if s.request is not None
               and s.request.state == DECODE
               and any(can_accept(self.engines[j], s.request)
                       for j in self._drain_peers(i, s.request))]
        if hot:
            id_lists = [s.table[:src.blocks.blocks_for(s.context_len)]
                        for s in hot]
            t0 = time.perf_counter()
            with src._mesh_ctx():
                sets = extract_block_sets(
                    src._pools, id_lists,
                    d_pools=src._d_pools if src.speculative else None)
            share = (time.perf_counter() - t0) / len(hot)
            prefetched = {s.request.rid: bs
                          for s, bs in zip(hot, sets)}
        for rid in resident_rids:
            if rid in src.finished:
                continue
            slot = next((s for s in src.sched.slots
                         if s.request is not None
                         and s.request.rid == rid), None)
            if slot is None:
                continue
            req = slot.request
            cand = self._drain_peers(i, req)
            cand = [j for j in cand if can_accept(self.engines[j], req)]
            if not cand:
                residents_in_place += 1
                continue
            j = self._least_loaded(cand)
            try:
                info = migrate_request(src, self.engines[j], rid,
                                       prefetched=prefetched.get(rid),
                                       extract_s=share)
            except TransportError:
                residents_in_place += 1
                continue
            if info is None:
                continue            # finished at the pipeline flush
            self._owner[rid] = j
            self.migrations += 1
            migrated += 1
        obs.serve("drain", replica=i, requeued=len(moved),
                  migrated=migrated,
                  residents_in_place=residents_in_place,
                  placement=self.placement)
        return moved

    def _drain_peers(self, i: int, req: Request) -> list[int]:
        """Where a draining replica's resident may go: any admitting
        sibling on a mixed fleet; on a disaggregated one, a DECODE
        resident goes to the decode side (even off a prefill replica —
        it is exactly a finished prefill awaiting handoff) and a
        mid-prefill one to another prefill replica."""
        if self.roles is None:
            return [j for j in self._admitting() if j != i]
        want = ("decode"
                if req.state == DECODE or req.swap_set is not None
                else "prefill")
        return [j for j in self._admitting()
                if j != i and self.role_of[j] == want]

    def restart(self, i: int) -> None:
        """Re-admit to a drained replica (its pools/caches/compiled
        steps were never torn down — restart is instant)."""
        if i not in self._draining:
            raise ValueError(f"replica {i} is not draining")
        self._draining.discard(i)
        obs.serve("restart", replica=i)

    def run(self) -> dict[int, Request]:
        """Drive the fleet until every submitted request finishes;
        returns the merged {rid: Request}. A single-replica router
        delegates to the engine's own :meth:`~.engine.ServeEngine.run`
        — no router events, no replica tags: the telemetry stream is
        byte-identical to the pre-router engine's (the ``--replicas 1``
        contract). A multi-replica run emits one report event per
        replica (each tagged) and ONE aggregate router report LAST, so
        report consumers that keep the last event
        (``obs/report.py::_serve_summary``) see the fleet view."""
        if self.n == 1:
            return dict(self.engines[0].run())
        self.warmup()
        with obs.span("serve/router_run"):
            while self.has_work():
                self.step()
        for eng in self.engines:
            obs.scalar(
                "serve/kv_peak_utilization",
                eng.blocks.peak_used / max(eng.blocks.num_blocks - 1, 1))
            summary = eng.slo_summary()
            if summary:
                obs.serve("report", **summary)
        summary = self.slo_summary()
        if summary:
            obs.serve("report", **summary)
        return self.finished

    # -- aggregates ----------------------------------------------------------

    def replica_load_imbalance(self) -> Optional[float]:
        """max/mean requests served per replica (1.0 = perfectly even;
        worse UP — the figure ``obsctl diff`` gates as
        ``serve_replica_load_imbalance``). None before any finish."""
        served = [len(eng.finished) for eng in self.engines]
        mean = sum(served) / len(served)
        if mean == 0:
            return None
        return max(served) / mean

    def slo_summary(self) -> dict:
        """The fleet-level SLO summary ({} until a request finishes;
        pass-through to the engine's own for a single-replica router):
        aggregate TTFT/e2e percentiles over every replica's finished
        requests, fleet counters (drains/requeues, summed preemptions
        and tokens), ``replica_load_imbalance``, the aggregate decode
        tokens/sec from the engines' own decode accounting, the
        aggregate prefix-cache hit rate, and a compact ``per_replica``
        breakdown (requests / peak waiting depth / pool peak / hit
        rate) — the figures the ``scripts/serve.py`` summary and the
        bench line surface."""
        if self.n == 1:
            out = self.engines[0].slo_summary()
            # the rate-limit counter lives router-side (rejections
            # never reach an engine) — ride it on the pass-through,
            # gated like every ISSUE 20 rider
            if self.rate_limited and out:
                out = dict(out, rate_limited=self.rate_limited)
            return out
        reqs = [r for eng in self.engines for r in eng.finished.values()]
        if not reqs:
            return {}
        from huggingface_sagemaker_tensorflow_distributed_tpu.obs.report import (
            percentile,
        )

        out: dict = {
            "requests": len(reqs),
            "replicas": self.n,
            "placement": self.placement,
            "tokens": sum(e.tokens_generated for e in self.engines),
            "iterations": sum(e.iterations for e in self.engines),
            "preemptions": sum(e.sched.n_preemptions
                               for e in self.engines),
            "peak_waiting_depth": max(e.peak_waiting
                                      for e in self.engines),
            "drains": self.drains,
            "requeues": self.requeues,
        }
        # cross-engine transport (ISSUE 18): absent on migration-free
        # fleets — the byte-identity contract
        mig_out = sum(e.migrations_out for e in self.engines)
        if mig_out:
            out["migrations"] = mig_out
            out["migration_bytes"] = sum(
                e.migration_bytes for e in self.engines)
            out["migration_restore_s"] = round(
                sum(e.migration_restore_s for e in self.engines), 6)
            # fleet tracing (ISSUE 19): the tail price of one transport
            # hop (source extraction stamp -> destination scatter
            # complete), pooled over every engine's observed hops —
            # absent when tracing is off (no samples), so untraced
            # fleets keep their PR 18 report bytes
            hops = sorted(h for e in self.engines
                          for h in e.transport_hop_s)
            if hops:
                out["transport_hop_s_p99"] = round(
                    percentile(hops, 0.99), 6)
        imb = self.replica_load_imbalance()
        if imb is not None:
            out["replica_load_imbalance"] = round(imb, 4)
        # open-loop SLO attainment (ISSUE 16): fleet attainment from the
        # summed per-engine counters (each engine already counted its
        # own finishes), the merged per-group split, and the summed
        # per-replica backlog peaks — an UPPER BOUND on the
        # instantaneous fleet backlog (the replicas need not have
        # peaked at the same iteration). Gated like the engines' own
        # keys: absent entirely on closed-loop fleets.
        if any(e._has_slo for e in self.engines):
            met = sum(e._slo_met for e in self.engines)
            total = sum(e._slo_total for e in self.engines)
            if total:
                out["slo_attainment"] = round(met / total, 4)
                groups: dict = {}
                for eng in self.engines:
                    for g, (m, t) in eng._group_slo.items():
                        acc = groups.setdefault(g, [0, 0])
                        acc[0] += m
                        acc[1] += t
                out["group_slo_attainment"] = {
                    g: round(m / t, 4)
                    for g, (m, t) in sorted(groups.items()) if t}
        if any(e._has_arrivals for e in self.engines):
            out["arrival_backlog_peak"] = sum(
                e._arrival_backlog_peak for e in self.engines)
        # admission policy (ISSUE 20): fleet rollups, gated exactly
        # like the engines' own riders — fifo / unlimited / deadline-
        # less fleets report byte-identically to the pre-policy router
        if self.policy != "fifo":
            out["policy"] = self.policy
            out["aging_promotions"] = sum(
                e.sched.aging_promotions for e in self.engines)
        if self.rate_limited:
            out["rate_limited"] = self.rate_limited
        dl_total = sum(e._deadline_total for e in self.engines)
        if dl_total:
            out["deadline_miss_frac"] = round(
                sum(e._deadline_miss for e in self.engines)
                / dl_total, 4)
        if any(e._has_priorities for e in self.engines):
            prios: dict = {}
            for eng in self.engines:
                for p, (m, t) in eng._priority_slo.items():
                    acc = prios.setdefault(p, [0, 0])
                    acc[0] += m
                    acc[1] += t
            if prios:
                out["priority_slo_attainment"] = {
                    str(p): round(m / t, 4)
                    for p, (m, t) in sorted(prios.items()) if t}
        if self.placement == "affinity":
            out["affinity_fallbacks"] = self.affinity_fallbacks
        dtok = sum(e.decode_tokens for e in self.engines)
        dsec = sum(e.decode_time_s for e in self.engines)
        if dsec > 0:
            out["decode_tokens_per_sec"] = round(dtok / dsec, 1)
        if self.engines[0].prefix_cache:
            admitted = sum(r.prefix_prompt_tokens for r in reqs)
            cached = sum(r.prefix_cached_tokens for r in reqs)
            out["prefix_cache"] = True
            out["prefix_cached_tokens"] = cached
            out["cache_hit_rate"] = (round(cached / admitted, 4)
                                     if admitted else 0.0)
        per_replica = []
        for i, eng in enumerate(self.engines):
            row = {
                "replica": i,
                "requests": len(eng.finished),
                "peak_waiting_depth": eng.peak_waiting,
                "preemptions": eng.sched.n_preemptions,
                "kv_peak_utilization": round(
                    eng.blocks.peak_used
                    / max(eng.blocks.num_blocks - 1, 1), 4),
            }
            if self.roles is not None:
                row["role"] = self.role_of[i]
            hit = eng._aggregate_hit_rate()
            if hit is not None:
                row["cache_hit_rate"] = round(hit, 4)
            per_replica.append(row)
        out["per_replica"] = per_replica
        if self.roles is not None:
            out["roles"] = (f"prefill:{self.roles['prefill']},"
                            f"decode:{self.roles['decode']}")
            if "slo_attainment" in out:
                # the disaggregation bench/diff metric: the fleet's
                # attainment UNDER role separation, named apart so
                # `obsctl diff` can gate disaggregated runs distinctly
                out["disagg_slo_attainment"] = out["slo_attainment"]
            out["per_role"] = self._per_role(reqs)
        ttfts = sorted(r.ttft_s for r in reqs if r.ttft_s is not None)
        e2es = sorted(r.finish_t - r.submit_t for r in reqs
                      if r.finish_t is not None and r.submit_t is not None)
        for label, vals in (("ttft", ttfts), ("e2e", e2es)):
            if not vals:
                continue
            out[f"{label}_p50_s"] = round(percentile(vals, 0.50), 6)
            out[f"{label}_p95_s"] = round(percentile(vals, 0.95), 6)
            out[f"{label}_p99_s"] = round(percentile(vals, 0.99), 6)
        return out

    def _per_role(self, reqs) -> dict:
        """Per-role attribution for a disaggregated fleet (ISSUE 18).
        Every request prefills on the prefill side and decodes on the
        decode side, so the split is by PHASE, not by request: the
        prefill row carries the fleet's TTFT percentiles (first tokens
        are emitted by the final prefill chunk) and the decode row the
        TPOT percentiles plus the aggregate decode tokens/sec — the
        two figures the bench line's no-worse-than-mixed side gates
        read."""
        from huggingface_sagemaker_tensorflow_distributed_tpu.obs.report import (
            percentile,
        )

        def pcts(row, label, vals):
            vals = sorted(vals)
            if vals:
                row[f"{label}_p50_s"] = round(percentile(vals, 0.50), 6)
                row[f"{label}_p95_s"] = round(percentile(vals, 0.95), 6)
                row[f"{label}_p99_s"] = round(percentile(vals, 0.99), 6)

        out = {}
        for role in ("prefill", "decode"):
            ids = [i for i in range(self.n) if self.role_of[i] == role]
            engs = [self.engines[i] for i in ids]
            row: dict = {
                "replicas": ids,
                "prefill_chunks": sum(e.prefill_chunks for e in engs),
                "prefill_dispatches": sum(e.prefill_dispatches
                                          for e in engs),
                "decode_steps": sum(e.decode_steps for e in engs),
                "migrations_out": sum(e.migrations_out for e in engs),
                "migrations_in": sum(e.migrations_in for e in engs),
            }
            if role == "prefill":
                pcts(row, "ttft",
                     (r.ttft_s for r in reqs if r.ttft_s is not None))
            else:
                pcts(row, "tpot",
                     ((r.finish_t - r.first_token_t)
                      / max((len(r.prompt) - r.orig_prompt_len)
                            + len(r.output) - 1, 1)
                      for r in reqs
                      if r.finish_t is not None
                      and r.first_token_t is not None))
                dtok = sum(e.decode_tokens for e in engs)
                dsec = sum(e.decode_time_s for e in engs)
                if dsec > 0:
                    row["decode_tokens_per_sec"] = round(dtok / dsec, 1)
            out[role] = row
        return out

    @contextlib.contextmanager
    def draining(self, i: int):
        """``with router.draining(i):`` — drain on entry, restart on
        exit (the rolling-restart shape)."""
        self.drain(i)
        try:
            yield self
        finally:
            self.restart(i)
