"""Serving entry point: drive the continuous-batching engine
(``serve/engine.py``) over a request trace and report per-request
latency + aggregate throughput.

Requests come from ``--input_file`` (JSONL, one
``{"prompt_ids": [...], "max_new_tokens": N}`` per line, optionally
carrying per-request ``temperature``/``top_k``/``top_p``/``seed``) or a
synthetic mixed-length trace (default — the zero-egress smoke path).
``--temperature/--top_k/--top_p/--sample_seed`` set the default
sampling configuration (greedy when temperature is 0);
``--gather_buckets`` overrides the gather-width ladder (decode and prefill)
(``HSTD_SERVE_GATHER_BUCKETS``; ``full`` disables bucketing);
``--prefix_cache on|off`` (``HSTD_SERVE_PREFIX_CACHE``, default on)
controls copy-on-write prompt-prefix KV sharing — per-request output
rows carry ``prefix_cached_tokens`` and the summary line the aggregate
cache hit rate + peak shared-block count. The model is
a randomly-initialized GPT-2 shape by default (``--model_dir`` loads an
exported causal-lm checkpoint the way ``scripts/predict.py`` does).

  # synthetic trace on the smoke model, engine knobs explicit
  python scripts/serve.py --requests 32 --num_slots 8 --block_size 16 \
      --prefill_chunk 16

  # real checkpoint
  python scripts/serve.py --model_dir /path/to/export \
      --input_file requests.jsonl

One JSON line per finished request (ids, TTFT, decode tokens/sec), then
one summary line (aggregate tokens/sec, TTFT percentiles, KV-pool peak
utilization, preemptions). With ``HSTD_TELEMETRY_DIR`` set, the engine
additionally streams ``serve`` lifecycle events + spans through ``obs``.
``--timeline on`` (``HSTD_SERVE_TIMELINE``, default on) adds
per-request lifecycle tracing: each output row carries its phase
decomposition (queue/prefill/decode/preempted seconds), the summary the
run-wide phase fractions + queue-wait p99, and the telemetry stream the
``request_timeline``/``iteration_ledger`` events that ``obsctl
timeline|slo|tail`` consume. ``--tp N`` (``HSTD_SERVE_TP``, default 1)
serves TENSOR-PARALLEL: params + KV pools shard over N devices (pools
on their heads axis — ``num_kv_heads % N == 0`` required), output
stays token-identical to the single-device engine, and the per-device
KV byte budget buys ~N× the resident requests; rows and the summary
carry ``tp``, the summary additionally ``kv_pool_bytes_per_device``.

``--replicas N --placement round_robin|least_loaded|affinity``
(``HSTD_SERVE_REPLICAS`` / ``HSTD_SERVE_PLACEMENT``, default
1/round_robin) serves MULTI-REPLICA (ISSUE 14): N engine replicas —
each its own scheduler/pool/prefix cache — behind one router with SLO-
and prefix-affinity-aware placement. Output is token-identical to a
single-engine run under every policy (placement cannot change tokens);
with N > 1 each per-request row carries its ``replica`` and the
summary the fleet view (``placement``, ``replica_load_imbalance``,
per-replica hit-rate/depth aggregates). ``--replicas 1`` is the
byte-identical single-engine path, telemetry included.

``--arrival poisson:RATE|bursty:HI,LO,P|closed`` (``HSTD_SERVE_ARRIVAL``
+ ``HSTD_SERVE_ARRIVAL_SEED``, default closed) serves OPEN-LOOP
(ISSUE 16): the trace arrives on a seeded schedule through
``serve/loadgen.py``'s wall-clock driver instead of all at once, so
offered load no longer self-throttles on engine backpressure.
``--slo ttft:SECS[,tpot:SECS]`` (``HSTD_SERVE_SLO_TTFT_S`` /
``HSTD_SERVE_SLO_TPOT_S``) attaches per-request deadlines — each
output row then carries ``slo_met``/``slack_s`` and the summary the
run's ``slo_attainment``, goodput tokens, per-group split and
dominant miss phase (the figures ``obsctl goodput`` recomputes from
the telemetry stream). ``--slo`` without ``--arrival`` judges the
closed-loop trace from submit time.

``--roles prefill:N,decode:M`` (``HSTD_SERVE_ROLES``, default off)
serves DISAGGREGATED (ISSUE 18): N prefill-only replicas run chunked
prefill at the full token budget and hand each finished request's live
KV block set to the least-loaded decode replica over
``serve/transport.py`` — zero re-prefill, token-identical output. The
summary gains ``roles``, ``migrations``/``migration_bytes`` and a
``per_role`` breakdown (prefill-side TTFT percentiles, decode-side
TPOT percentiles + tokens/sec). Requires ``--replicas`` unset or equal
to N+M. The same transport powers ``Router.drain``: draining a replica
now live-migrates its RESIDENT requests to siblings mid-decode instead
of waiting them out, so rolling restarts are preemption-free.

``--swap auto|always|never|off`` (``HSTD_SERVE_SWAP``, default off)
turns on the host-RAM KV spill tier (ISSUE 17): preemption victims
swap their KV block sets to host and restore on re-admit without
re-prefill (``auto`` picks swap vs recompute per victim from the
bytes-moved vs weight-traffic estimate), and zero-ref prefix-cache
blocks demote to host before true eviction, reviving on match.
``--swap_bytes N`` (``HSTD_SERVE_SWAP_BYTES``, 0 = unbounded) caps the
host tier. With the tier on, the summary carries ``swap_policy``,
swap traffic (``swap_outs``/``swap_ins``/``swap_bytes``/``restore_s``),
``recompute_tokens_avoided`` and the demote tier's
``host_tier_hits``/``host_tier_hit_rate``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def load_model(args):
    import jax.numpy as jnp

    from huggingface_sagemaker_tensorflow_distributed_tpu.models.auto import (
        init_params,
    )
    from huggingface_sagemaker_tensorflow_distributed_tpu.models.gpt2 import (
        Gpt2Config,
        Gpt2LMHeadModel,
    )

    if args.model_dir:
        from huggingface_sagemaker_tensorflow_distributed_tpu.models import (
            auto as auto_models,
        )
        model, params, _family, _config = auto_models.from_pretrained(
            args.model_dir, task="causal-lm")
        return model, params
    cfg = Gpt2Config(vocab_size=1024, hidden_size=256, num_layers=4,
                     num_heads=4, intermediate_size=1024,
                     max_position_embeddings=512, hidden_dropout=0.0,
                     embd_dropout=0.0, attention_dropout=0.0,
                     eos_token_id=1023, pad_token_id=0,
                     dtype=jnp.float32)
    model = Gpt2LMHeadModel(cfg)
    return model, init_params(model, cfg, seed=0)


def _sampling_kw(row, defaults, where: str) -> dict:
    """Per-request sampling fields from one JSONL row, validated
    LOUDLY: a drifted trace (bool/string/fractional top_k) must name
    its line, not silently serve different truncation than specified.
    JSON null (and absence) mean "use the CLI default"."""
    kw = {}
    for k, default in defaults.items():
        raw = row.get(k)
        if raw is None:
            kw[k] = default
            continue
        if isinstance(raw, bool) or not isinstance(raw, (int, float)):
            raise SystemExit(
                f"serve: {where}: field {k!r} must be a number, "
                f"got {raw!r}")
        if isinstance(default, int) and raw != int(raw):
            raise SystemExit(
                f"serve: {where}: field {k!r} must be an integer, "
                f"got {raw!r}")
        kw[k] = type(default)(raw)
    return kw


def load_trace(args, vocab: int):
    """[(prompt_ids, max_new_tokens, sampling_kwargs)] — per-request
    JSONL fields override the CLI-wide sampling defaults."""
    defaults = {"temperature": args.temperature, "top_k": args.top_k,
                "top_p": args.top_p, "seed": args.sample_seed}
    if args.input_file:
        trace = []
        with open(args.input_file, "r", encoding="utf-8") as f:
            for lineno, line in enumerate(f, 1):
                if not line.strip():
                    continue
                row = json.loads(line)
                kw = _sampling_kw(row, defaults,
                                  f"{args.input_file}:{lineno}")
                # graftlint: allow[R2] host-side JSONL decode before the engine exists — nothing device-resident to block on
                trace.append((np.asarray(row["prompt_ids"], np.int32),
                              int(row.get("max_new_tokens",
                                          args.max_new_tokens)), kw))
        return trace
    return [(p, m, dict(defaults)) for p, m in _synthetic_trace(args, vocab)]


def _synthetic_trace(args, vocab: int):
    """[(prompt_ids, max_new_tokens)] drawn from ``--seed``: every
    fourth request wants a long continuation, the rest a short one."""
    rng = np.random.RandomState(args.seed)
    short_new = (4, max(4, args.max_new_tokens // 4))
    long_new = (args.max_new_tokens // 2, args.max_new_tokens)
    trace = []
    for i in range(args.requests):
        p = int(rng.randint(args.prompt_min, args.prompt_max + 1))
        lo, hi = long_new if i % 4 == 3 else short_new
        prompt = rng.randint(1, vocab, (p,)).astype(np.int32)
        trace.append((prompt, int(rng.randint(lo, hi + 1))))
    return trace


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--model_dir", default=None)
    parser.add_argument("--input_file", default=None,
                        help="JSONL of {prompt_ids, max_new_tokens}")
    parser.add_argument("--requests", type=int, default=32,
                        help="synthetic-trace request count")
    parser.add_argument("--prompt_min", type=int, default=8)
    parser.add_argument("--prompt_max", type=int, default=48)
    parser.add_argument("--max_new_tokens", type=int, default=64)
    parser.add_argument("--num_slots", type=int, default=8)
    parser.add_argument("--block_size", type=int, default=16)
    parser.add_argument("--num_blocks", type=int, default=0,
                        help="KV pool blocks incl. the null block "
                             "(0 = 3/4 of slots * max_model_len)")
    parser.add_argument("--prefill_chunk", type=int, default=16)
    parser.add_argument("--prefill_batch", type=int, default=4,
                        help="max prefilling slots packed per dispatch")
    parser.add_argument("--max_model_len", type=int, default=0,
                        help="0 = model max_position_embeddings")
    parser.add_argument("--gather_buckets", default=None,
                        help="gather-width ladder (decode and prefill), e.g. "
                             "'64,256' ('full' disables bucketing; "
                             "default: HSTD_SERVE_GATHER_BUCKETS or "
                             "quarter+full width)")
    parser.add_argument("--speculate_k", type=int, default=None,
                        help="speculative decode: draft tokens per "
                             "verify window (default: "
                             "HSTD_SERVE_SPECULATE_K or 0 = off)")
    parser.add_argument("--draft_layers", type=int, default=None,
                        help="layer-skip self-draft depth (default: "
                             "HSTD_SERVE_DRAFT_LAYERS or a quarter of "
                             "the target's layers)")
    parser.add_argument("--prefix_cache", default=None,
                        choices=("on", "off"),
                        help="copy-on-write prompt-prefix KV sharing "
                             "across requests (default: "
                             "HSTD_SERVE_PREFIX_CACHE or on)")
    parser.add_argument("--kernel", default=None,
                        choices=("xla", "pallas"),
                        help="force the decode attention path: xla = "
                             "gather + dense (reference), pallas = fused "
                             "paged kernel (interpret mode off-TPU). "
                             "Default: HSTD_SERVE_KERNEL, else the engine "
                             "chooses: the kernel on a TPU for K/V pools "
                             "of 128-wide heads in a floating type "
                             "without --tp, the gather anywhere else")
    parser.add_argument("--kv_cache_dtype", default=None,
                        choices=("fp", "int8"),
                        help="KV pool storage; int8 halves pool bytes "
                             "per decode step (default: "
                             "HSTD_SERVE_KV_DTYPE or the model config)")
    parser.add_argument("--timeline", default=None,
                        choices=("on", "off"),
                        help="per-request lifecycle tracing "
                             "(request_timeline/iteration_ledger "
                             "events + phase decomposition in the "
                             "summary; default: HSTD_SERVE_TIMELINE "
                             "or on)")
    parser.add_argument("--tp", type=int, default=None,
                        help="tensor-parallel degree: shard params + "
                             "KV pools (heads axis) over this many "
                             "devices so one engine serves models "
                             "bigger than a chip; num_kv_heads must "
                             "divide (rejected loudly otherwise) and "
                             "the KV byte budget re-denominates per "
                             "device (default: HSTD_SERVE_TP or 1)")
    parser.add_argument("--replicas", type=int, default=None,
                        help="multi-replica serving: engine replicas "
                             "behind the placement router; each "
                             "replica owns its scheduler/KV pool/"
                             "prefix cache, output stays token-"
                             "identical to one engine (default: "
                             "HSTD_SERVE_REPLICAS or 1 = the byte-"
                             "identical single-engine path)")
    parser.add_argument("--placement", default=None,
                        choices=("round_robin", "least_loaded",
                                 "affinity"),
                        help="replica placement policy: round_robin, "
                             "least_loaded (live waiting-depth + KV-"
                             "pressure gauges), or affinity (route to "
                             "the replica holding the longest cached "
                             "prefix, imbalance-bounded; default: "
                             "HSTD_SERVE_PLACEMENT or round_robin)")
    parser.add_argument("--roles", default=None,
                        help="disaggregated prefill/decode fleet, "
                             "prefill:N,decode:M — prefill-only "
                             "replicas hand finished KV block sets to "
                             "decode replicas over the transport "
                             "primitive, token-identically (default: "
                             "HSTD_SERVE_ROLES or off = mixed "
                             "replicas)")
    parser.add_argument("--overlap", default=None,
                        choices=("on", "off"),
                        help="dispatch-ahead decode loop: host "
                             "scheduling overlaps the in-flight "
                             "device step, device_get deferred one "
                             "iteration; off restores the serial "
                             "loop byte-for-byte (default: "
                             "HSTD_SERVE_OVERLAP or on)")
    parser.add_argument("--arrival", default=None,
                        help="open-loop arrival process: poisson:RATE "
                             "(req/s), bursty:RATE_HI,RATE_LO,P_SWITCH "
                             "(Markov-modulated), or closed = submit "
                             "the whole trace up front (default: "
                             "HSTD_SERVE_ARRIVAL or closed; schedule "
                             "seed: HSTD_SERVE_ARRIVAL_SEED)")
    parser.add_argument("--slo", default=None,
                        help="per-request deadline targets, "
                             "ttft:SECS[,tpot:SECS] or none: rows gain "
                             "slo_met/slack_s, the summary "
                             "slo_attainment + miss attribution "
                             "(default: HSTD_SERVE_SLO_TTFT_S / "
                             "HSTD_SERVE_SLO_TPOT_S)")
    parser.add_argument("--policy", default=None,
                        choices=("fifo", "slo"),
                        help="admission-ordering policy: fifo = strict "
                             "arrival order, slo = earliest effective "
                             "deadline folding in priority class, "
                             "predicted demand (prefix-cache aware) "
                             "and a bounded aging term (default: "
                             "HSTD_SERVE_POLICY or fifo)")
    parser.add_argument("--aging_s", type=float, default=None,
                        help="starvation bound for --policy slo: a "
                             "request waiting this long is promoted "
                             "ahead of all unpromoted work (default: "
                             "HSTD_SERVE_AGING_S or 30)")
    parser.add_argument("--rate_limit", default=None,
                        help="per-tenant token-bucket admission caps, "
                             "GROUP=RATE[:BURST],... req/s keyed on "
                             "each request's group tag ('*' = default "
                             "bucket); over-budget submits get a "
                             "structured rate_limited rejection, "
                             "never a silent drop")
    parser.add_argument("--swap", default=None,
                        choices=("auto", "always", "never", "off"),
                        help="host-RAM KV spill tier: swap preemption "
                             "victims to host + demote evicted prefix "
                             "blocks (auto = per-victim bytes-vs-"
                             "recompute estimate; never = demotion "
                             "only; default: HSTD_SERVE_SWAP or off)")
    parser.add_argument("--swap_bytes", type=int, default=None,
                        help="host-tier byte budget shared by demoted "
                             "payloads and swap reservations "
                             "(default: HSTD_SERVE_SWAP_BYTES or "
                             "0 = unbounded)")
    parser.add_argument("--temperature", type=float, default=0.0,
                        help="0 = greedy (the default); > 0 samples")
    parser.add_argument("--top_k", type=int, default=0)
    parser.add_argument("--top_p", type=float, default=0.0)
    parser.add_argument("--sample_seed", type=int, default=0,
                        help="per-request sampling seed default")
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args()

    import jax

    from huggingface_sagemaker_tensorflow_distributed_tpu import obs
    from huggingface_sagemaker_tensorflow_distributed_tpu.parallel import (
        device_memory_peaks,
        enable_compilation_cache,
        require_accelerator,
    )
    from huggingface_sagemaker_tensorflow_distributed_tpu.serve.loadgen import (
        OpenLoopDriver,
        bursty_arrivals,
        parse_arrival,
        parse_arrival_seed,
        parse_slo,
        poisson_arrivals,
    )
    from huggingface_sagemaker_tensorflow_distributed_tpu.serve.router import (
        Router,
        parse_roles,
    )

    try:
        arrival = parse_arrival(args.arrival)
        arrival_seed = parse_arrival_seed()
        slo_spec = parse_slo(args.slo)
        roles = parse_roles(args.roles)
    except ValueError as e:
        raise SystemExit(f"serve: {e}")

    obs.configure()
    tracker = obs.compile_tracker()      # None with telemetry off
    device = require_accelerator()
    enable_compilation_cache()
    model, params = load_model(args)
    max_len = args.max_model_len or (
        model.config.max_position_embeddings
        // args.block_size) * args.block_size
    num_blocks = args.num_blocks or (
        1 + args.num_slots * (max_len // args.block_size) * 3 // 4)
    # the router is the one construction path: replicas=1 (the
    # default) is a pass-through whose engine behavior AND telemetry
    # stream are byte-identical to building the ServeEngine directly
    router = Router(model, params, replicas=args.replicas,
                    placement=args.placement, roles=roles,
                    num_slots=args.num_slots,
                    block_size=args.block_size, num_blocks=num_blocks,
                    prefill_chunk=args.prefill_chunk,
                    prefill_batch=args.prefill_batch,
                    max_model_len=max_len,
                    gather_buckets=args.gather_buckets,
                    speculate_k=args.speculate_k,
                    draft=args.draft_layers,
                    prefix_cache=args.prefix_cache,
                    kernel=args.kernel,
                    kv_cache_dtype=args.kv_cache_dtype,
                    timeline=args.timeline,
                    overlap=args.overlap,
                    mesh=args.tp,
                    swap=args.swap,
                    swap_bytes=args.swap_bytes,
                    policy=args.policy,
                    aging_s=args.aging_s,
                    rate_limit=args.rate_limit)
    engine = router.engines[0]
    trace = load_trace(args, model.config.vocab_size - 1)
    # precompile the sampled step variants too when the trace will
    # sample, so no request pays a mid-serve compile
    router.warmup(sampled=any(kw.get("temperature", 0) > 0
                              for _, _, kw in trace))
    # every executable the trace needs exists now: a compile counted
    # from here on is a mid-serve stall
    compiles_at_warm = tracker.count if tracker else None
    driver = None
    if arrival is not None:
        # open loop: the trace arrives on the seeded schedule through
        # the wall-clock driver — arrival_s + the SLO thread into
        # submit, so the engine stamps real verdicts into telemetry
        proc, pp = arrival
        if proc == "poisson":
            arrivals = poisson_arrivals(pp["rate"], len(trace),
                                        arrival_seed)
            rate = pp["rate"]
        else:
            arrivals = bursty_arrivals(pp["rate_hi"], pp["rate_lo"],
                                       pp["p_switch"], len(trace),
                                       arrival_seed)
            rate = pp["rate_hi"]
        schedule = [
            (a, {"prompt": p, "max_new_tokens": m, **kw})
            for a, (p, m, kw) in zip(arrivals, trace)]
        driver = OpenLoopDriver(router, schedule, clock="wall",
                                slo=slo_spec, process=proc, rate=rate)
        t0 = time.perf_counter()
        finished = driver.run()
        wall = time.perf_counter() - t0
        reqs = [finished[rid] for rid in sorted(finished)]
    else:
        reqs, rejected = [], 0
        for p, m, kw in trace:
            r = router.submit(p, m, slo=slo_spec, **kw)
            if getattr(r, "rejected", False):
                rejected += 1
            else:
                reqs.append(r)
        t0 = time.perf_counter()
        router.run()
        wall = time.perf_counter() - t0

    # what the run was measured on, carried by both summary shapes
    run_facts = {
        **device,
        "compiles_after_warmup": (tracker.count - compiles_at_warm
                                  if tracker else None),
        "param_bytes_per_device": sum(
            leaf.addressable_shards[0].data.nbytes
            for leaf in jax.tree.leaves(engine.params)),
        "peak_bytes_in_use": device_memory_peaks()}
    total = 0
    for req in reqs:
        ids = router.output_ids(req)
        total += len(ids)
        row = {
            "request": req.rid, "prompt_len": req.orig_prompt_len,
            "output_ids": [int(t) for t in ids],
            "ttft_s": round(req.ttft_s, 4) if req.ttft_s else None,
            "sampled": req.sampled, "seed": req.seed,
            "preemptions": req.preemptions, "tp": engine.tp}
        if req.has_slo:
            # the engine's own verdict (stamped at finish): deadline
            # met, and the worst axis's margin in seconds
            row["slo_met"] = req.slo_met
            row["slack_s"] = req.slack_s
        if req.deadline_s is not None:
            row["deadline_s"] = req.deadline_s
            row["deadline_miss"] = req.deadline_miss
        if req.priority:
            row["priority"] = req.priority
        if router.n > 1:
            row["replica"] = router.replica_of(req)
        if engine.speculative:
            row["acceptance_rate"] = (
                round(req.spec_accepted / req.spec_proposed, 4)
                if req.spec_proposed else None)
        if engine.prefix_cache:
            row["prefix_cached_tokens"] = req.prefix_cached_tokens
        if engine.timeline:
            # the request's own phase decomposition (what its
            # request_timeline telemetry event carries in full)
            row["phase_s"] = {ph: round(v, 4)
                              for ph, v in req.phase_s.items()}
        print(json.dumps(row))
    # open-loop / SLO summary fields (absent on a plain closed run):
    # the driver's goodput accounting — the same figures `obsctl
    # goodput` recomputes offline from the telemetry stream
    open_extra = {}
    if slo_spec is not None:
        open_extra["slo"] = {"ttft_s": slo_spec.ttft_s,
                             "tpot_s": slo_spec.tpot_s}
    if driver is not None:
        dsum = driver.summary()
        open_extra["arrival"] = {"process": dsum["process"],
                                 "rate": dsum.get("rate"),
                                 "seed": arrival_seed,
                                 "clock": dsum["clock"]}
        for k in ("slo_attainment", "slo_met", "slo_missed",
                  "goodput_tokens", "group_slo_attainment",
                  "miss_phases", "dominant_miss_phase",
                  "rate_limited", "deadline_misses",
                  "deadline_miss_frac"):
            if k in dsum:
                open_extra[k] = dsum[k]
    elif rejected:
        open_extra["rate_limited"] = rejected
    if router.n > 1:
        # fleet summary (ISSUE 14): the router's own aggregate (the
        # same figures its final `serve` report telemetry event
        # carries) plus summed engine counters — per-replica hit-rate/
        # depth aggregates ride `per_replica`
        rslo = router.slo_summary()
        stats_all = [e.stats() for e in router.engines]
        print(json.dumps({
            "summary": True,
            "requests": len(reqs),
            "tokens": total,
            "tokens_per_sec": round(total / wall, 1),
            "replicas": router.n,
            "placement": router.placement,
            "drains": router.drains,
            "requeues": router.requeues,
            "replica_load_imbalance": rslo.get("replica_load_imbalance"),
            "affinity_fallbacks": (router.affinity_fallbacks
                                   if router.placement == "affinity"
                                   else None),
            "ttft_p50_s": rslo.get("ttft_p50_s"),
            "ttft_p95_s": rslo.get("ttft_p95_s"),
            "ttft_p99_s": rslo.get("ttft_p99_s"),
            "e2e_p50_s": rslo.get("e2e_p50_s"),
            "e2e_p95_s": rslo.get("e2e_p95_s"),
            "e2e_p99_s": rslo.get("e2e_p99_s"),
            "peak_waiting_depth": rslo.get("peak_waiting_depth"),
            "decode_steps": sum(s.decode_steps for s in stats_all),
            "decode_tokens_per_sec": rslo.get("decode_tokens_per_sec"),
            "prefill_chunks": sum(s.prefill_chunks for s in stats_all),
            "preemptions": sum(s.preemptions for s in stats_all),
            "gather_buckets": engine.gather_buckets,
            "prefix_cache": engine.prefix_cache,
            "cache_hit_rate": rslo.get("cache_hit_rate"),
            "timeline": engine.timeline,
            "overlap": engine.overlap,
            "kernel": engine.kernel,
            "kv_dtype": engine.kv_cache_dtype,
            "tp": engine.tp,
            "per_replica": rslo.get("per_replica"),
            **run_facts,
            **({"roles": rslo.get("roles"),
                "per_role": rslo.get("per_role"),
                "migrations": router.migrations,
                "migration_bytes": sum(
                    s.migration_bytes for s in stats_all)}
               if router.roles is not None else {}),
            **({"swap_policy": engine.swap,
                "swap_outs": sum(s.swap_outs for s in stats_all),
                "swap_ins": sum(s.swap_ins for s in stats_all),
                "swap_bytes": sum(s.swap_bytes for s in stats_all),
                "recompute_tokens_avoided": sum(
                    s.recompute_tokens_avoided for s in stats_all),
                "host_tier_hits": sum(
                    s.host_tier_hits for s in stats_all)}
               if engine.swap != "off" else {}),
            **({"arrival_backlog_peak":
                rslo.get("arrival_backlog_peak")}
               if driver is not None else {}),
            **({"slo_attainment": rslo.get("slo_attainment"),
                "group_slo_attainment":
                rslo.get("group_slo_attainment")}
               if slo_spec is not None and driver is None else {}),
            **({"policy": router.policy,
                "aging_promotions": rslo.get("aging_promotions")}
               if router.policy != "fifo" else {}),
            **({"deadline_miss_frac": rslo.get("deadline_miss_frac")}
               if rslo.get("deadline_miss_frac") is not None else {}),
            **({"priority_slo_attainment":
                rslo.get("priority_slo_attainment")}
               if rslo.get("priority_slo_attainment") else {}),
            **open_extra}))
        obs.flush()
        return
    stats = engine.stats()
    # SLO summary from the engine's own accounting (the same figures
    # its final `serve` report telemetry event carries): TTFT + e2e
    # latency percentiles and scheduler gauges
    slo = engine.slo_summary()
    print(json.dumps({
        "summary": True,
        "requests": len(reqs),
        "tokens": total,
        "tokens_per_sec": round(total / wall, 1),
        "ttft_p50_s": slo.get("ttft_p50_s"),
        "ttft_p95_s": slo.get("ttft_p95_s"),
        "ttft_p99_s": slo.get("ttft_p99_s"),
        "e2e_p50_s": slo.get("e2e_p50_s"),
        "e2e_p95_s": slo.get("e2e_p95_s"),
        "e2e_p99_s": slo.get("e2e_p99_s"),
        "peak_waiting_depth": slo.get("peak_waiting_depth"),
        "decode_steps": stats.decode_steps,
        "decode_tokens_per_sec": round(
            stats.decode_tokens / stats.decode_time_s, 1)
        if stats.decode_time_s > 0 else None,
        "prefill_chunks": stats.prefill_chunks,
        "prefill_dispatches": stats.prefill_dispatches,
        "preemptions": stats.preemptions,
        "gather_buckets": engine.gather_buckets,
        "bucket_switches": stats.bucket_switches,
        "gather_read_waste_peak": round(stats.gather_waste_peak, 3),
        "gather_read_waste_mean": round(stats.gather_waste_mean, 3),
        "speculate_k": engine.speculate_k or None,
        "acceptance_rate": (round(stats.acceptance_rate, 4)
                            if stats.acceptance_rate is not None else None),
        "verify_read_waste_mean": (round(stats.verify_waste_mean, 3)
                                   if engine.speculative else None),
        "prefix_cache": engine.prefix_cache,
        "cache_hit_rate": (round(stats.cache_hit_rate, 4)
                           if stats.cache_hit_rate is not None else None),
        "blocks_shared_peak": (stats.blocks_shared_peak
                               if engine.prefix_cache else None),
        "blocks_saved_peak": (stats.blocks_saved_peak
                              if engine.prefix_cache else None),
        "cow_copies": stats.cow_copies if engine.prefix_cache else None,
        "timeline": engine.timeline,
        "queue_wait_p99_s": slo.get("queue_wait_p99_s"),
        "queue_time_frac": slo.get("queue_time_frac"),
        "prefill_time_frac": slo.get("prefill_time_frac"),
        "decode_time_frac": slo.get("decode_time_frac"),
        "preempted_time_frac": slo.get("preempted_time_frac"),
        "overhead_time_frac": slo.get("overhead_time_frac"),
        "overlap": engine.overlap,
        "overlap_flushes": (stats.overlap_flushes
                            if engine.overlap else None),
        # the loop's own account of its wall time (seconds): host work
        # before/inside/after the dispatches, the wait for the device,
        # and the caller's time between iterations
        "host_loop": {k: round(v, 6) for k, v
                      in engine.host_loop_totals().items()},
        "kernel": stats.kernel,
        "kv_dtype": stats.kv_dtype,
        "tp": stats.tp,
        "kv_pool_bytes_per_device": stats.kv_pool_bytes_per_device or None,
        "kv_bytes_read_per_step": (round(
            stats.kv_bytes_read / stats.decode_steps, 1)
            if stats.decode_steps else None),
        "kv_peak_utilization": round(stats.kv_peak_utilization, 3),
        **run_facts,
        **({"swap_policy": stats.swap_policy,
            "swap_outs": stats.swap_outs,
            "swap_ins": stats.swap_ins,
            "swap_bytes": stats.swap_bytes,
            "restore_s": round(stats.restore_s, 6),
            "recompute_tokens_avoided": stats.recompute_tokens_avoided,
            "host_tier_hits": stats.host_tier_hits,
            "host_tier_hit_rate": (
                round(stats.host_tier_hit_rate, 4)
                if stats.host_tier_hit_rate is not None else None)}
           if engine.swap != "off" else {}),
        **({"arrival_backlog_peak": slo.get("arrival_backlog_peak")}
           if driver is not None else {}),
        **({"slo_attainment": slo.get("slo_attainment"),
            "group_slo_attainment": slo.get("group_slo_attainment")}
           if slo_spec is not None and driver is None else {}),
        **({"policy": engine.policy,
            "aging_promotions": slo.get("aging_promotions")}
           if engine.policy != "fifo" else {}),
        **({"deadline_miss_frac": slo.get("deadline_miss_frac")}
           if slo.get("deadline_miss_frac") is not None else {}),
        **({"priority_slo_attainment":
            slo.get("priority_slo_attainment")}
           if slo.get("priority_slo_attainment") else {}),
        **open_extra}))
    obs.flush()


if __name__ == "__main__":
    main()
