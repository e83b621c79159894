"""Telemetry event schema: the stable contract every emitter writes and
every consumer (BENCH harness, ``scripts/check_telemetry_schema.py``,
Perfetto via ``trace.json``) parses.

Deliberately stdlib-only and import-light: the schema validator must run
in environments without jax (CI lint steps, the driver box), so nothing
in this module — or anything it imports — may touch jax.

One event = one JSON object on one line of ``events.jsonl``. Envelope
fields present on EVERY event:

    v     int    schema version (SCHEMA_VERSION)
    t     float  unix wall-clock seconds at emission (for a span, which
                 is written in batches: when its batch was written; a
                 span's own times are ``mono`` and ``dur``)
    host  int    process index (rank); 0 on single-host runs
    pid   int    OS process id
    type  str    one of EVENT_TYPES

Per-type required fields are in ``REQUIRED_FIELDS``; extra fields are
always allowed (forward compatibility), missing required fields are a
schema error. ``trace.json`` is the Chrome-trace-viewer projection of
the span events: ``{"traceEvents": [{"name", "ph": "X", "ts", "dur",
"pid", "tid"}, ...]}`` with timestamps in microseconds.
"""

from __future__ import annotations

import json
from typing import Iterator, Optional

SCHEMA_VERSION = 1

_NUM = (int, float)

# type name -> {field: allowed python types}
REQUIRED_FIELDS: dict[str, dict[str, tuple]] = {
    # a completed wall-time span; "mono" is the monotonic start time
    # (seconds since the process's telemetry state was made) so spans
    # order/nest without wall-clock steps — order spans by it, never
    # by the envelope "t"
    "span": {"name": (str,), "dur": _NUM, "mono": _NUM, "tid": (int,)},
    # one wake-up of the host-pause meter (obs/watchdog.py PauseMeter)
    # that came more than its threshold late: "mono" is when it was
    # due, on the spans' clock, "dur" by how much it was late — for
    # that long no Python of the process ran
    "host_pause": {"mono": _NUM, "dur": _NUM},
    # one scalar sample of a named series (loss, lr, samples/sec, ...)
    "metric": {"name": (str,), "value": _NUM + (type(None),)},
    # liveness: emitted every HSTD_HEARTBEAT_SECS by the heartbeat thread
    "heartbeat": {"uptime": _NUM, "progress": (int,), "progress_age": _NUM},
    # the heartbeat's stall dump: all thread stacks at the moment the
    # watched thread stopped pulsing
    "stall": {"progress_age": _NUM, "stalled": (str,), "threads": (list,)},
    # one XLA compilation, from jax.monitoring ("event" is the jax key)
    "compile": {"event": (str,), "dur": _NUM, "count": (int,), "cum": _NUM},
    # one device.memory_stats() sample (TPU/GPU; never emitted on CPU)
    "memory": {"device": (str,), "stats": (dict,)},
    # one controller decision from the input-pipeline autotuners
    # (data/autotune.py prefetch depth; data/streaming.py read-coalesce
    # gap): "name" is the tuned knob, "depth" its new integer value,
    # "reason" the trigger (input_bound / compute_bound / mem_cap /
    # waste_high / waste_low)
    "autotune": {"name": (str,), "depth": (int,), "reason": (str,)},
    # a budget/threshold warning (e.g. compile_budget when cumulative XLA
    # compile seconds exceed HSTD_COMPILE_BUDGET_S); mirrored to stderr
    "alert": {"name": (str,), "message": (str,)},
    # one anomaly-detector trigger (obs/anomaly.py): "name" is the kind
    # (nan_loss / nan_grad / grad_explosion / step_time_spike /
    # straggler / heartbeat_stall), "message" the human-readable
    # diagnosis; extras ride along ("step", "evidence" = the flight
    # dump path, "profile_dir" = the profiler capture, kind-specific
    # numbers). Rate-limited at the source — one per incident, not per
    # observation
    "anomaly": {"name": (str,), "message": (str,)},
    # one serving-engine lifecycle event (serve/engine.py): "event" is
    # submit / admit / first_token / finish / preempt / bucket_switch /
    # report; per-request events also carry an integer "request" id,
    # first_token / finish carry the latency/accounting extras
    # (ttft_s, tokens), submit carries "sampled" and bucket_switch
    # carries "gather_bucket" (typed below when present)
    "serve": {"event": (str,)},
    # run metadata, first event after configure()
    "run": {"argv": (list,)},
    # one compiled hot-path program by the program's own modules
    # (obs/programs.py; written once a program at obs.flush/shutdown):
    # "program" is the short name a trace gives its module
    # (`prefill_chunk` of `jit__prefill_chunk`), "key" the shapes that
    # tell programs of one name apart (rows, width | bucket, slots |
    # batch), "scopes" the distinct `[module path, pass]` pairs (numbered
    # layers collapsed, pass "" | "fwd" | "bwd"), "ops" every instruction
    # that runs as an operation of its own: `name -> [scope index (-1:
    # none), component, result type, mixed]`, a fifth element listing a
    # mixed fusion's other components; "resolve_s" what lowering and
    # compiling (or finding) it took, "from_cache" whether the
    # persistent compilation cache had it
    "program_map": {"program": (str,), "key": (dict,), "scopes": (list,),
                    "ops": (dict,), "resolve_s": _NUM,
                    "from_cache": (bool,)},
}

# the components a program_map files an operation under
PROGRAM_COMPONENTS = ("mixer", "ffn", "residual", "head", "embed", "cache",
                      "optimizer", "collective", "other")

# optional per-type fields that are TYPE-CHECKED when present (absence
# is fine — they ride specific event subtypes): the serve engine's
# decode gather-width bucket, the per-request sampling flag, the
# speculative-decode acceptance accounting, and the prefix-cache
# accounting (admit/finish events carry the per-request figures —
# prompt tokens served from shared KV blocks and the hit rate; the
# final report event the aggregates + block-sharing peaks)
OPTIONAL_FIELDS: dict[str, dict[str, tuple]] = {
    # the registry's own name of the program, the HLO module's name,
    # the program's own jax.named_scopes the text carried (an executable
    # a cache held from before they were added carries none), whether
    # the program differentiates (a train step)
    "program_map": {"name": (str,), "module": (str,), "refined_by": (list,),
                    "training": (bool,)},
    # nesting: how many spans enclose this one on its thread, and the
    # innermost one's name (absent on a top-level span)
    "span": {"depth": (int,), "parent": (str,), "args": (dict,)},
    "serve": {"gather_bucket": (int,), "sampled": (bool,),
              "request": (int,), "speculate_k": (int,),
              # per-event context riders surfaced by graftlint R4
              # (ISSUE 15): submit's token budget, admit's slot/queue
              # placement, preempt's cause, and bucket_switch's
              # from/to context — emitted since their PRs but never
              # declared, i.e. exactly the silent schema drift the
              # telemetry-field-contract rule now fails in the diff
              "max_new_tokens": (int,),
              "slot": (int,),
              "queue_depth": (int,),
              "reason": (str,),
              "prev_bucket": (int,),
              "max_context": (int,),
              "draft_proposed": (int,), "draft_accepted": (int,),
              "acceptance_rate": _NUM,
              "verify_read_waste_peak": _NUM,
              "verify_read_waste_mean": _NUM,
              # the option's bool; "off (recurrent state)" where the
              # index stands down for a model with recurrent state
              "prefix_cache": (bool, str),
              "prefix_cached_tokens": (int,),
              "cache_hit_rate": _NUM,
              "blocks_shared_peak": (int,),
              "blocks_saved_peak": (int,),
              "cow_copies": (int,),
              "prefix_evictions": (int,),
              "shared_read_frac": _NUM,
              # paged-attention kernel + int8 KV pools (finish events
              # and the final report carry the engine's decode-kernel
              # and pool-storage modes; the report additionally the
              # mean pool bytes one decode dispatch reads — the figure
              # int8 pools halve)
              "kernel": (str,),
              # which way decode steps attend (ISSUE 29): the fused
              # paged kernel or the gathered copy of the cache
              "decode_path": (str,),
              # how prefill dispatches write their chunks back (ISSUE
              # 38): "pages" (whole blocks) | "rows" (one a token)
              "write_path": (str,),
              "kv_dtype": (str,),
              "kv_bytes_read": (int,),
              "kv_bytes_read_per_step": _NUM,
              # dispatch-ahead serving loop (ISSUE 12): the report
              # event carries the overlap mode + how many times the
              # pipeline was force-drained (preemption / KV-pressure
              # block math must act on committed state); absent
              # entirely with HSTD_SERVE_OVERLAP=off, whose stream is
              # byte-identical to the serial engine's
              "overlap": (bool,),
              "overlap_flushes": (int,),
              # tensor-parallel serving (ISSUE 13): finish events and
              # the final report carry the engine's mesh degree; the
              # report additionally the KV pool's PER-DEVICE byte
              # footprint (block count × per-device block bytes — the
              # figure sharding divides by tp, and what `obsctl diff`
              # gates as serve_kv_pool_bytes_per_device)
              "tp": (int,),
              "kv_pool_bytes_per_device": (int,),
              # multi-replica serving router (ISSUE 14): per-request
              # lifecycle events + request_timeline + per-replica
              # reports carry the owning replica index (what `obsctl
              # slo` groups tail attribution by); the router's
              # aggregate report carries the fleet shape (replicas /
              # placement), the drain/requeue counters, the max/mean
              # requests-served imbalance `obsctl diff` gates, and a
              # compact per-replica breakdown; drain/requeue/restart
              # events carry the move itself (source replica, count,
              # destination)
              "replica": (int,),
              "replicas": (int,),
              "placement": (str,),
              "requeued": (int,),
              "to_replica": (int,),
              "drains": (int,),
              "requeues": (int,),
              "replica_load_imbalance": _NUM,
              "affinity_fallbacks": (int,),
              "per_replica": (list,),
              # request-lifecycle tracing (ISSUE 10): the
              # `request_timeline` event's five-way phase decomposition
              # (queue + prefill + decode + preempted + overhead sums
              # to e2e) + coalesced segment list, the per-iteration
              # `iteration_ledger` fields, and the per-tenant grouping
              # key — all host-side stamps, all typed when present so
              # a drifted emitter can't poison `obsctl timeline|slo|
              # tail` silently
              "at": (str,),
              "group": (str,),
              "e2e_s": _NUM,
              "ttft_s": _NUM,
              "queue_s": _NUM,
              "prefill_s": _NUM,
              "decode_s": _NUM,
              "preempted_s": _NUM,
              "overhead_s": _NUM,
              "segments": (list,),
              "tokens": (int,),
              "prompt_len": (int,),
              "preemptions": (int,),
              "blocked_iters": (int,),
              "blocked_reason": (str,),
              "iteration": (int,),
              "dur_s": _NUM,
              # the iteration's own account of its wall time (ISSUE
              # 25): four disjoint parts of dur_s — host work before a
              # dispatch, time inside the jitted calls, time blocked on
              # a device fetch, host work after a fetch — so that
              # dur_s >= their sum on every ledger line, and the
              # caller's time since the previous iteration returned
              "stage_s": _NUM,
              # routed experts and the latent cache (ISSUE 28), on the
              # ledger lines and the report of a model that has them:
              # (token, expert) pairs of the real tokens of the
              # dispatches whose counts a fetch has passed, those that
              # landed on the experts held here (<= moe_pairs on every
              # line), and per expert layer of the decode step among
              # them: held experts that got a pair, the busiest and the
              # mean held expert's pairs
              "moe_pairs": (int,),
              "moe_pairs_held": (int,),
              "moe_decode_pairs": (int,),
              "moe_decode_pairs_held": (int,),
              "moe_experts_touched": (list,),
              "moe_expert_load_max": (list,),
              "moe_expert_load_mean": (list,),
              "latent_bytes_per_token": (int,),
              # hyper-connections under a sigmoid gate (ISSUE 35), on the
              # report of a model that has them: residual streams a
              # token and the gate's name; on its ledger lines the
              # largest |row or column sum - 1| of any H_res the
              # iteration's landed dispatches made
              "residual_streams": (int,),
              "gate": (str,),
              "mhc_defect_max": _NUM,
              # recurrent state (ISSUE 33), on the ledger lines and the
              # report of a model that has it: rows whose state the
              # iteration's dispatches read and wrote (prefill rows +
              # decode slots), the tokens its decode dispatch attended,
              # the real prompt tokens of its prefill dispatches,
              # the most slots that held a request at once; and on the
              # report the bytes of one slot's state, of the state pools
              # and of one resident token's K/V
              "state_slots": (int,),
              "kv_tokens_resident": (int,),
              "prefill_tokens": (int,),
              "state_slots_peak": (int,),
              "state_bytes_per_slot": (int,),
              "state_pool_bytes": (int,),
              "kv_token_bytes": (int,),
              # how its one-token steps advance the state (ISSUE 36):
              # "kernel" (ops/pallas_gated_delta.py) | "xla"
              "state_step": (str,),
              # a latent-attention model's report (ISSUE 32): prefill
              # dispatches by how the expanded form attended
              # ({"kernel": n, "xla_loop": n})
              "prefill_dispatches_by_form": (dict,),
              "dispatch_s": _NUM,
              "fetch_wait_s": _NUM,
              "commit_s": _NUM,
              "gap_s": _NUM,
              "prefill_chunks": (int,),
              "prefill_dispatches": (int,),
              # prefill's gather bucket (ISSUE 26): Σ over real rows of
              # start + chunk against Σ over dispatches of rows × bucket
              # width — the iteration's share on a ledger line, the
              # run's sums on the report; needed <= attended always
              "prefill_keys_needed": (int,),
              "prefill_keys_attended": (int,),
              "decode_slots": (int,),
              "waiting": (int,),
              "kv_used_frac": _NUM,
              "queue_wait_p50_s": _NUM,
              "queue_wait_p99_s": _NUM,
              "queue_time_frac": _NUM,
              "prefill_time_frac": _NUM,
              "decode_time_frac": _NUM,
              "preempted_time_frac": _NUM,
              "overhead_time_frac": _NUM,
              # open-loop load + SLO attainment (ISSUE 16): submit
              # events carry the ARRIVAL timestamp (distinct from the
              # submit stamp — queue wait decomposes into pre-submit
              # backlog + in-engine queue) and the request's deadline
              # targets; finish + request_timeline events the per-
              # request verdicts (slo_met and the per-target splits,
              # slack_s = the tightest remaining margin, negative on a
              # miss); the iteration ledger the count of arrived-but-
              # unadmitted requests; the report event the aggregate
              # attainment (the DistServe goodput numerator), its
              # per-tenant breakdown, and the backlog peak `obsctl
              # diff` gates. The `open_loop` driver event stamps each
              # loadgen run with its arrival process / rate / clock so
              # `obsctl goodput` can split a rate sweep into runs
              "arrival_s": _NUM,
              "slo_ttft_s": _NUM,
              "slo_tpot_s": _NUM,
              "slo_met": (bool,),
              "ttft_slo_met": (bool,),
              "tpot_slo_met": (bool,),
              "slack_s": _NUM,
              "slo_attainment": _NUM,
              "group_slo_attainment": (dict,),
              "arrival_backlog": (int,),
              "arrival_backlog_peak": (int,),
              "process": (str,),
              "rate": _NUM,
              "clock": (str,),
              "requests": (int,),
              # host-RAM KV spill tier (ISSUE 17): swap_out / swap_in
              # events carry the per-victim transfer (bytes moved; the
              # restore additionally its scatter seconds and the
              # re-prefill tokens it avoided), and the report event the
              # run aggregates — the policy in force, swap traffic
              # totals, and the demote tier's hit accounting (what
              # `obsctl diff` gates as serve_swap_bytes /
              # serve_host_tier_hit_rate). Absent entirely with
              # HSTD_SERVE_SWAP=off — that stream is byte-identical to
              # the pre-tier engine's
              "swap_policy": (str,),
              "swap_outs": (int,),
              "swap_ins": (int,),
              "swap_bytes": (int,),
              "restore_s": _NUM,
              "recompute_tokens_avoided": (int,),
              "host_tier_hits": (int,),
              "host_tier_hit_rate": _NUM,
              # cross-engine KV transport (ISSUE 18): `migrate` events
              # carry one move (source/destination replica, payload
              # bytes, destination scatter seconds ride the existing
              # restore_s key); `drain` events gain the migrated /
              # residents_in_place split; report events the fleet
              # totals, the role spec, the per-role attribution
              # breakdown, and the disaggregated attainment `obsctl
              # diff` gates as serve_disagg_slo_attainment /
              # serve_migration_bytes. All absent on migration-free
              # runs — the byte-identity contract
              "from_replica": (int,),
              "migration_bytes": (int,),
              "migrated": (int,),
              "residents_in_place": (int,),
              "migrations": (int,),
              "migrations_in": (int,),
              "migrations_out": (int,),
              "migration_restore_s": _NUM,
              "roles": (str,),
              "role": (str,),
              "per_role": (dict,),
              "disagg_slo_attainment": _NUM,
              # fleet-level distributed tracing (ISSUE 19): the
              # router-minted trace context every lifecycle event of a
              # traced request carries — `trace_id` names the request
              # fleet-wide, `hop` counts its inter-engine moves (0 on
              # the placement engine; migrate/requeue advance it).
              # Hot `migrate` events additionally price the hop:
              # `transport_hop_s` (source extraction stamp ->
              # destination scatter complete) with `extract_s` split
              # out so the stitcher (obs/trace.py) can telescope pure
              # data movement against admission wait. The bench's
              # `trace_stitch` summary event and the router report's
              # transport_hop_s_p99 rider carry the fleet aggregates
              # `obsctl diff` gates. All absent on untraced runs —
              # the byte-identity contract.
              "trace_id": (str,),
              "hop": (int,),
              "extract_s": _NUM,
              "transport_hop_s": _NUM,
              "transport_hop_s_p99": _NUM,
              "traces": (int,),
              "complete_traces": (int,),
              "trace_stitch_failures": (int,),
              # goodput-aware admission control (ISSUE 20): submit
              # events carry the request's deadline/priority riders,
              # finish events the end-to-end `deadline_miss` verdict,
              # `rate_limited` events the router's structured
              # per-tenant rejection (retry_after_s is the bucket's
              # time-to-next-token), and report events the fleet
              # rollups (`policy`, aging promotion count, miss
              # fraction, per-priority-class attainment). All absent
              # under the default fifo policy with no deadlines,
              # priorities, or rate limits — the byte-identity
              # contract
              "policy": (str,),
              "deadline_s": _NUM,
              "priority": (int,),
              "deadline_miss": (bool,),
              "rate_limited": (int,),
              "retry_after_s": _NUM,
              "aging_promotions": (int,),
              "deadline_miss_frac": _NUM,
              "priority_slo_attainment": (dict,)},
}

# The serve-event vocabulary: every literal first argument an
# `obs.serve(...)` call site may pass. graftlint's telemetry-contract
# rule (analysis/rules.py R4) extracts this tuple STATICALLY (it must
# stay a pure literal) and flags any emitter inventing an event kind
# outside it — the same no-silent-drift contract the field registry
# above enforces for kwargs.
SERVE_EVENTS = (
    "submit", "admit", "first_token", "finish", "preempt",
    "bucket_switch", "report", "request_timeline", "iteration_ledger",
    "open_loop", "swap_out", "swap_in", "migrate", "drain", "requeue",
    "restart", "trace_stitch", "rate_limited",
)

EVENT_TYPES = tuple(REQUIRED_FIELDS)

ENVELOPE_FIELDS: dict[str, tuple] = {
    "v": (int,),
    "t": _NUM,
    "host": (int,),
    "pid": (int,),
    "type": (str,),
}


def _program_map_errors(obj: dict) -> list[str]:
    """What the field types cannot say of a ``program_map``: every scope
    is ``[path, pass]`` and every operation's row names a scope that
    exists and a component that does. At most three faults are named."""
    errors = []
    scopes = obj["scopes"]
    for i, scope in enumerate(scopes):
        if not (isinstance(scope, list) and len(scope) == 2
                and isinstance(scope[0], str)
                and scope[1] in ("", "fwd", "bwd")):
            errors.append(f"program_map: scopes[{i}] is not "
                          "[path, '' | 'fwd' | 'bwd']")
    for name, row in obj["ops"].items():
        if len(errors) >= 3:
            break
        if not (isinstance(row, list) and len(row) in (4, 5)
                and isinstance(row[0], int) and not isinstance(row[0], bool)
                and isinstance(row[2], str) and isinstance(row[3], bool)):
            errors.append(f"program_map: ops[{name!r}] is not [scope index, "
                          "component, result type, mixed]")
        elif row[1] not in PROGRAM_COMPONENTS:
            errors.append(f"program_map: ops[{name!r}] has the unknown "
                          f"component {row[1]!r}")
        elif not -1 <= row[0] < len(scopes):
            errors.append(f"program_map: ops[{name!r}] names scope "
                          f"{row[0]} of {len(scopes)}")
    return errors


def validate_event(obj: object) -> list[str]:
    """Schema errors for one decoded event (empty list = valid)."""
    if not isinstance(obj, dict):
        return [f"event is {type(obj).__name__}, not an object"]
    errors = []
    for field, types in ENVELOPE_FIELDS.items():
        if field not in obj:
            errors.append(f"missing envelope field {field!r}")
        elif not isinstance(obj[field], types) or isinstance(obj[field], bool):
            errors.append(f"envelope field {field!r} has type "
                          f"{type(obj[field]).__name__}")
    etype = obj.get("type")
    if isinstance(etype, str):
        required = REQUIRED_FIELDS.get(etype)
        if required is None:
            errors.append(f"unknown event type {etype!r} "
                          f"(known: {', '.join(EVENT_TYPES)})")
        else:
            for field, types in required.items():
                if field not in obj:
                    errors.append(f"{etype}: missing field {field!r}")
                elif (not isinstance(obj[field], types)
                      or (isinstance(obj[field], bool)
                          and bool not in types)):
                    errors.append(f"{etype}: field {field!r} has type "
                                  f"{type(obj[field]).__name__}")
            for field, types in OPTIONAL_FIELDS.get(etype, {}).items():
                val = obj.get(field)
                if val is None:
                    continue
                if (not isinstance(val, types)
                        or (isinstance(val, bool) and bool not in types)):
                    errors.append(f"{etype}: optional field {field!r} "
                                  f"has type {type(val).__name__}")
    if etype == "program_map" and not errors:
        errors.extend(_program_map_errors(obj))
    if obj.get("v") not in (None, SCHEMA_VERSION):
        errors.append(f"schema version {obj.get('v')!r} != {SCHEMA_VERSION}")
    return errors


def iter_events(path: str, strict_tail: bool = False) -> Iterator[tuple[int, Optional[dict], Optional[str]]]:
    """Yield ``(lineno, event_or_None, error_or_None)`` per line.

    Crash tolerance: a process killed mid-write leaves at most one torn
    FINAL line, which is skipped silently (unless ``strict_tail``); a
    torn line anywhere else means corruption and is reported.
    """
    with open(path, "r", encoding="utf-8", errors="replace") as f:
        lines = f.read().splitlines()
    for i, line in enumerate(lines):
        if not line.strip():
            continue
        try:
            yield i + 1, json.loads(line), None
        except ValueError:
            if i == len(lines) - 1 and not strict_tail:
                continue  # torn tail from a mid-write kill: expected
            yield i + 1, None, "unparseable JSON"


def validate_events_file(path: str, strict_tail: bool = False) -> tuple[int, list[str]]:
    """(valid_event_count, error messages) for an events.jsonl file."""
    count = 0
    errors: list[str] = []
    for lineno, obj, err in iter_events(path, strict_tail=strict_tail):
        if err is not None:
            errors.append(f"{path}:{lineno}: {err}")
            continue
        errs = validate_event(obj)
        if errs:
            errors.extend(f"{path}:{lineno}: {e}" for e in errs)
        else:
            count += 1
    return count, errors


def validate_trace_file(path: str) -> tuple[int, list[str]]:
    """(event_count, error messages) for a Chrome-trace trace.json."""
    try:
        with open(path, "r", encoding="utf-8") as f:
            doc = json.load(f)
    except ValueError as e:
        return 0, [f"{path}: unparseable JSON ({e})"]
    events = doc.get("traceEvents") if isinstance(doc, dict) else doc
    if not isinstance(events, list):
        return 0, [f"{path}: expected a traceEvents list"]
    errors = []
    for i, ev in enumerate(events):
        if not isinstance(ev, dict):
            errors.append(f"{path}: traceEvents[{i}] is not an object")
            continue
        for field, types in (("name", (str,)), ("ph", (str,)),
                             ("ts", _NUM), ("pid", (int,)), ("tid", (int,))):
            if not isinstance(ev.get(field), types):
                errors.append(f"{path}: traceEvents[{i}] bad {field!r}")
        if ev.get("ph") == "X" and not isinstance(ev.get("dur"), _NUM):
            errors.append(f"{path}: traceEvents[{i}] complete event "
                          "without numeric 'dur'")
    return len(events), errors
