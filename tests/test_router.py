"""Multi-replica serving router (ISSUE 14): placement must change
WHERE a request runs, never WHAT it emits — per-request output is
token-identical to a single-engine run under every policy, across a
forced mid-trace drain, and under the randomized submit/drain/restart
conservation schedule (every submitted request finishes exactly once,
block pools restored free on every replica). The ``replicas=1`` router
is allowlist-gated byte-identical to the pre-router engine stream.
"""

import numpy as np
import pytest

from huggingface_sagemaker_tensorflow_distributed_tpu import obs
from huggingface_sagemaker_tensorflow_distributed_tpu.serve.router import (
    Router,
    parse_placement,
    parse_replicas,
)


_KW = dict(num_slots=2, block_size=4, num_blocks=40, prefill_chunk=8,
           max_model_len=64)


def _trace(seed=0, n=6):
    rng = np.random.RandomState(seed)
    lens = [(5, 7), (9, 3), (12, 10), (5, 4), (9, 8), (7, 6),
            (11, 5), (6, 9)][:n]
    return [(rng.randint(1, 120, (p,)).astype(np.int32), m)
            for p, m in lens]


def _single_outputs(model, params, trace, **kw):
    from huggingface_sagemaker_tensorflow_distributed_tpu.serve.engine import (
        ServeEngine,
    )

    eng = ServeEngine(model, params, **kw)
    reqs = [eng.submit(p, m) for p, m in trace]
    eng.run()
    return [list(eng.output_ids(r)) for r in reqs]


@pytest.mark.parametrize("placement",
                         ["round_robin", "least_loaded", "affinity"])
def test_router_output_token_identical_to_single_engine(gpt2_setup,
                                                        placement):
    """The ISSUE 14 core contract: 2-replica output per request equals
    the single-engine run's under every placement policy (the engine's
    per-request exactness is placement-blind), and both replicas
    actually served traffic."""
    _cfg, model, params = gpt2_setup
    trace = _trace()
    base = _single_outputs(model, params, trace, **_KW)
    router = Router(model, params, replicas=2, placement=placement,
                    **_KW)
    reqs = [router.submit(p, m) for p, m in trace]
    router.run()
    assert [list(router.output_ids(q)) for q in reqs] == base
    owners = {router.replica_of(q) for q in reqs}
    assert owners == {0, 1}
    slo = router.slo_summary()
    assert slo["replicas"] == 2 and slo["placement"] == placement
    assert slo["requests"] == len(trace)
    assert slo["replica_load_imbalance"] >= 1.0
    if placement == "least_loaded":
        assert slo["replica_load_imbalance"] <= 1.5


def test_router_sampled_streams_bitwise_identical_across_placement(
        gpt2_setup):
    """Sampled requests are seeded per request, so placement cannot
    change the stream: bitwise-identical outputs single vs routed."""
    from huggingface_sagemaker_tensorflow_distributed_tpu.serve.engine import (
        ServeEngine,
    )

    _cfg, model, params = gpt2_setup
    rng = np.random.RandomState(3)
    trace = [(rng.randint(1, 120, (7,)).astype(np.int32), 6, 11 + i)
             for i in range(4)]
    eng = ServeEngine(model, params, **_KW)
    ereqs = [eng.submit(p, m, temperature=0.9, top_k=20, seed=s)
             for p, m, s in trace]
    eng.run()
    base = [list(eng.output_ids(r)) for r in ereqs]
    router = Router(model, params, replicas=2,
                    placement="least_loaded", **_KW)
    rreqs = [router.submit(p, m, temperature=0.9, top_k=20, seed=s)
             for p, m, s in trace]
    router.run()
    assert [list(router.output_ids(q)) for q in rreqs] == base


def test_router_drain_mid_trace_token_identical_and_conserving(
        gpt2_setup):
    """The drain acceptance gate: a forced mid-trace drain finishes
    EVERY request with outputs token-identical to an undrained run —
    waiting requests requeue to the sibling (recompute semantics),
    resident ones finish in place — and both replicas' block pools
    come back fully free."""
    _cfg, model, params = gpt2_setup
    trace = _trace(n=8)
    kw = dict(num_slots=2, block_size=4, num_blocks=14, prefill_chunk=8,
              max_model_len=64)
    base = _single_outputs(model, params, trace, **kw)

    router = Router(model, params, replicas=2, placement="round_robin",
                    **kw)
    reqs = [router.submit(p, m) for p, m in trace]
    router.warmup()
    for _ in range(2):
        router.step()
    moved = router.drain(0)
    assert moved, "drain must have found waiting requests to requeue"
    assert router.requeues == len(moved)
    assert all(router.replica_of(q) == 1 for q in moved)
    # draining the last admitting replica is an outage, not a drain
    with pytest.raises(ValueError):
        router.drain(1)
    router.run()
    assert [list(router.output_ids(q)) for q in reqs] == base
    assert len(router.finished) == len(trace)
    for eng in router.engines:
        assert eng.blocks.num_used == 0
        assert (eng.blocks.num_free + eng.blocks.num_cached
                == eng.blocks.num_blocks - 1)
    # restart re-admits: new traffic may land on replica 0 again
    router.restart(0)
    extra = [router.submit(p, m) for p, m in _trace(seed=9, n=4)]
    router.run()
    assert {router.replica_of(q) for q in extra} == {0, 1}


def test_router_conservation_under_random_drain_restart_schedule(
        gpt2_setup):
    """The ISSUE 14 conservation property: a randomized submit / step /
    drain / restart schedule across 3 replicas loses and duplicates
    NOTHING — every submitted request finishes exactly once somewhere,
    and every replica's block pool is restored free."""
    _cfg, model, params = gpt2_setup
    rng = np.random.RandomState(7)
    kw = dict(num_slots=2, block_size=4, num_blocks=14, prefill_chunk=8,
              max_model_len=64)
    router = Router(model, params, replicas=3, placement="least_loaded",
                    **kw)
    router.warmup()
    submitted = []
    for step_i in range(30):
        op = rng.rand()
        if op < 0.5 and len(submitted) < 16:
            p = rng.randint(1, 120, (int(rng.randint(4, 12)),))
            submitted.append(
                router.submit(p.astype(np.int32), int(rng.randint(2, 9))))
        elif op < 0.65:
            admitting = [i for i in range(3) if i not in router._draining]
            if len(admitting) > 1:
                router.drain(int(rng.choice(admitting)))
        elif op < 0.8 and router._draining:
            router.restart(int(rng.choice(sorted(router._draining))))
        if router.has_work():
            router.step()
    router.run()
    finished_sets = [set(e.finished) for e in router.engines]
    # exactly once: the per-replica finished sets are disjoint and
    # their union is exactly the submitted rid set
    assert sum(len(s) for s in finished_sets) == len(submitted)
    union = set().union(*finished_sets)
    assert union == {q.rid for q in submitted}
    assert all(len(router.output_ids(q)) > 0 for q in submitted)
    assert router.drains > 0
    for eng in router.engines:
        assert eng.blocks.num_used == 0
        assert (eng.blocks.num_free + eng.blocks.num_cached
                == eng.blocks.num_blocks - 1)


def test_router_affinity_keeps_families_sticky_and_aged(gpt2_setup):
    """Affinity placement: requests sharing a templated prefix land on
    one replica (the router-level fingerprint index, built from the
    same chain-key hashing as the BlockManager's prefix index), and
    the index ages — a tiny cap still serves exactly, it just forgets
    old families."""
    _cfg, model, params = gpt2_setup
    rng = np.random.RandomState(5)
    prefixes = [rng.randint(1, 120, (12,)).astype(np.int32)
                for _ in range(2)]
    trace = []
    for j in range(3):
        for f in range(2):
            tail = rng.randint(1, 120, (3,)).astype(np.int32)
            trace.append((np.concatenate([prefixes[f], tail]), 4))
    router = Router(model, params, replicas=2, placement="affinity",
                    **_KW)
    reqs = [router.submit(p, m) for p, m in trace]
    router.run()
    # family f = trace rows f, f+2, f+4: one replica each, distinct
    owners = [router.replica_of(q) for q in reqs]
    fam0, fam1 = owners[0::2], owners[1::2]
    assert len(set(fam0)) == 1 and len(set(fam1)) == 1
    assert set(fam0) != set(fam1)       # least-loaded seeded them apart
    assert router.affinity_fallbacks == 0
    # a capped index evicts oldest fingerprints but never affects
    # output correctness
    tiny = Router(model, params, replicas=2, placement="affinity",
                  affinity_cap=2, **_KW)
    treqs = [tiny.submit(p, m) for p, m in trace]
    tiny.run()
    assert len(tiny._affinity) <= 2
    assert ([list(tiny.output_ids(q)) for q in treqs]
            == [list(router.output_ids(q)) for q in reqs])


def test_router_affinity_hit_rate_at_least_round_robins(gpt2_setup):
    """Three templated families interleaved, each template primed:
    round-robin splits every family over both replicas and each pays
    its own cold miss; affinity keeps a family where it was primed.
    Same tokens, and a higher prefix hit rate."""
    _cfg, model, params = gpt2_setup
    rng = np.random.RandomState(8)
    prefixes = [rng.randint(1, 120, (16,)).astype(np.int32)
                for _ in range(3)]
    trace = [(np.concatenate(
        [prefixes[f], rng.randint(1, 120, (int(rng.randint(2, 6)),))
         .astype(np.int32)]), int(rng.randint(3, 6)))
        for _ in range(3) for f in range(3)]
    outs, hit = {}, {}
    for placement in ("round_robin", "affinity"):
        router = Router(model, params, replicas=2, placement=placement,
                        prefix_cache=True, **_KW)
        for p in prefixes:
            router.submit(p, 1)
        router.run()
        reqs = [router.submit(p, m) for p, m in trace]
        router.run()
        outs[placement] = [list(router.output_ids(q)) for q in reqs]
        hit[placement] = (sum(q.prefix_cached_tokens for q in reqs)
                          / sum(q.prefix_prompt_tokens for q in reqs))
    assert outs["affinity"] == outs["round_robin"]
    assert hit["affinity"] > hit["round_robin"] > 0


def test_router_affinity_imbalance_bound_falls_back_to_load(gpt2_setup):
    """Affinity never starves load balance: once the sticky replica is
    more than ``affinity_max_skew`` load units deeper than the
    lightest sibling, placement falls back to least-loaded."""
    _cfg, model, params = gpt2_setup
    rng = np.random.RandomState(6)
    prefix = rng.randint(1, 120, (12,)).astype(np.int32)
    router = Router(model, params, replicas=2, placement="affinity",
                    affinity_max_skew=2, **_KW)
    reqs = []
    for _ in range(6):   # same family, no stepping: queue 0 deepens
        tail = rng.randint(1, 120, (3,)).astype(np.int32)
        reqs.append(router.submit(np.concatenate([prefix, tail]), 3))
    owners = [router.replica_of(q) for q in reqs]
    sticky = owners[0]
    assert owners[1] == sticky           # affinity held while light
    assert (1 - sticky) in owners        # ...then the bound kicked in
    assert router.affinity_fallbacks > 0
    router.run()
    assert len(router.finished) == len(reqs)


def test_router_single_replica_is_byte_identical_passthrough(
        gpt2_setup, tmp_path):
    """The ``--replicas 1`` contract, allowlist-gated like
    ``overlap=off``: a 1-replica router's telemetry stream carries the
    SAME event sequence with the SAME key sets as the bare engine —
    no router event subtypes, no replica/placement keys anywhere, and
    nothing new in the SLO summary."""
    from huggingface_sagemaker_tensorflow_distributed_tpu.serve.engine import (
        ServeEngine,
    )

    _cfg, model, params = gpt2_setup
    trace = _trace(n=5)

    def run_instrumented(build):
        out = tmp_path / f"t{build.__name__}"
        obs.reset(out_dir=str(out), enabled=True)
        try:
            srv = build()
            for p, m in trace:
                srv.submit(p, m)
            srv.run()
            obs.flush()
        finally:
            obs.reset()
        events = [e for _, e, err in obs.iter_events(
            str(out / "events.jsonl")) if err is None]
        return srv, [e for e in events if e["type"] == "serve"]

    def engine():
        return ServeEngine(model, params, **_KW)

    def router():
        return Router(model, params, replicas=1, **_KW)

    eng, eng_ev = run_instrumented(engine)
    rt, rt_ev = run_instrumented(router)
    # identical event sequence: same kinds, same key sets, in order
    assert ([(e["event"], tuple(sorted(e))) for e in rt_ev]
            == [(e["event"], tuple(sorted(e))) for e in eng_ev])
    router_keys = {"replica", "replicas", "placement", "requeued",
                   "to_replica", "drains", "requeues",
                   "replica_load_imbalance", "per_replica",
                   "affinity_fallbacks"}
    for e in rt_ev:
        leaked = router_keys & set(e)
        assert not leaked, (e["event"], leaked)
    assert not any(k in rt.slo_summary() for k in router_keys)
    assert rt.engines[0].replica is None


def test_router_two_replica_stream_is_tagged_and_schema_valid(
        gpt2_setup, tmp_path):
    """With N > 1 every per-request lifecycle event (and the
    request_timeline) carries the owning ``replica``, the router run
    ends with per-replica reports plus ONE aggregate report (last —
    the one ``obs/report.py`` keeps), and the produced stream passes
    the schema validator."""
    _cfg, model, params = gpt2_setup
    out = tmp_path / "t2"
    obs.reset(out_dir=str(out), enabled=True)
    try:
        router = Router(model, params, replicas=2,
                        placement="round_robin", **_KW)
        reqs = [router.submit(p, m) for p, m in _trace(n=5)]
        router.run()
        obs.flush()
    finally:
        obs.reset()
    count, errors = obs.validate_events_file(str(out / "events.jsonl"))
    assert not errors and count > 0
    events = [e for _, e, err in obs.iter_events(
        str(out / "events.jsonl")) if err is None]
    serve = [e for e in events if e["type"] == "serve"]
    for kind in ("submit", "admit", "first_token", "finish",
                 "request_timeline"):
        rows = [e for e in serve if e.get("event") == kind]
        assert rows, kind
        assert all(isinstance(e.get("replica"), int) for e in rows), kind
    owners = {router.replica_of(q) for q in reqs}
    finishes = {e["replica"] for e in serve if e["event"] == "finish"}
    assert finishes == owners == {0, 1}
    reports = [e for e in serve if e.get("event") == "report"]
    assert len(reports) == 3             # 2 replica reports + aggregate
    assert [r.get("replica") for r in reports[:2]] == [0, 1]
    agg = reports[-1]
    assert agg["replicas"] == 2 and agg["placement"] == "round_robin"
    assert isinstance(agg["replica_load_imbalance"], float)
    assert isinstance(agg["per_replica"], list) and len(
        agg["per_replica"]) == 2
    # the merged cross-host report keeps the aggregate (last) view
    from huggingface_sagemaker_tensorflow_distributed_tpu.obs.report import (
        build_report,
    )

    rep = build_report([str(out)])
    assert rep["serve"]["replicas"] == 2
    assert rep["serve"]["replica_load_imbalance"] \
        == agg["replica_load_imbalance"]


def test_router_rejected_submit_leaves_placement_state_untouched(
        gpt2_setup):
    """A submit the scheduler rejects (over-length) must not advance
    the round-robin rotation or pollute the affinity index — placement
    state commits only for ACCEPTED requests."""
    _cfg, model, params = gpt2_setup
    rng = np.random.RandomState(4)
    too_long = rng.randint(1, 120, (60,)).astype(np.int32)  # +16 > 64
    ok = rng.randint(1, 120, (8,)).astype(np.int32)

    rr = Router(model, params, replicas=2, placement="round_robin",
                **_KW)
    with pytest.raises(ValueError):
        rr.submit(too_long, 16)
    assert rr._rr == 0
    first = rr.submit(ok, 3)
    assert rr.replica_of(first) == 0     # rotation starts unskewed

    aff = Router(model, params, replicas=2, placement="affinity", **_KW)
    with pytest.raises(ValueError):
        aff.submit(too_long, 16)
    assert not aff._affinity             # no fingerprints registered
    rr.run(), aff.run()


def test_router_knob_parsing(monkeypatch):
    assert parse_replicas(None) == 1
    assert parse_replicas("3") == 3
    monkeypatch.setenv("HSTD_SERVE_REPLICAS", "4")
    assert parse_replicas(None) == 4
    with pytest.raises(ValueError):
        parse_replicas("0")
    with pytest.raises(ValueError):
        parse_replicas("many")
    assert parse_placement(None) == "round_robin"
    assert parse_placement("AFFINITY") == "affinity"
    monkeypatch.setenv("HSTD_SERVE_PLACEMENT", "least_loaded")
    assert parse_placement(None) == "least_loaded"
    with pytest.raises(ValueError):
        parse_placement("random")


def test_router_affinity_speculative_prefix_composition(gpt2_setup):
    """The heaviest composition (slow tier, ISSUE 14 budget): affinity
    placement x speculative decode x prefix caching across 2 replicas
    stays token-identical to the same single speculative engine, with
    the per-replica prefix caches actually hitting."""
    from huggingface_sagemaker_tensorflow_distributed_tpu.serve.engine import (
        ServeEngine,
    )

    _cfg, model, params = gpt2_setup
    rng = np.random.RandomState(11)
    prefixes = [rng.randint(1, 120, (12,)).astype(np.int32)
                for _ in range(2)]
    trace = []
    for j in range(3):
        for f in range(2):
            tail = rng.randint(1, 120, (3,)).astype(np.int32)
            trace.append((np.concatenate([prefixes[f], tail]), 5))
    kw = dict(num_slots=2, block_size=4, num_blocks=60, prefill_chunk=8,
              max_model_len=64, speculate_k=2, draft=1,
              prefix_cache=True)
    eng = ServeEngine(model, params, **kw)
    ereqs = [eng.submit(p, m) for p, m in trace]
    eng.run()
    base = [list(eng.output_ids(r)) for r in ereqs]
    router = Router(model, params, replicas=2, placement="affinity",
                    **kw)
    rreqs = [router.submit(p, m) for p, m in trace]
    router.run()
    assert [list(router.output_ids(q)) for q in rreqs] == base
    slo = router.slo_summary()
    assert slo.get("cache_hit_rate", 0) > 0
    # sticky families: each family's requests share one replica
    owners = [router.replica_of(q) for q in rreqs]
    assert len(set(owners[0::2])) == 1 and len(set(owners[1::2])) == 1
