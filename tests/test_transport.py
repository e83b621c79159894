"""Cross-engine KV block-set transport (ISSUE 18): migration must
change WHERE a request decodes, never WHAT it emits — a resident moved
mid-decode (across a gather-bucket boundary, greedy or sampled) resumes
on the destination token-exactly with zero re-prefill; a randomized
two-engine submit/step/migrate schedule conserves every block on BOTH
pools at every step; ``Router.drain`` live-migrates residents so a
drain completes without waiting anything out; and the disaggregated
prefill/decode fleet keeps strict role separation while staying
token-identical to one engine.
"""

import numpy as np
import pytest

from huggingface_sagemaker_tensorflow_distributed_tpu import obs
from huggingface_sagemaker_tensorflow_distributed_tpu.serve.router import (
    Router,
    parse_roles,
)
from huggingface_sagemaker_tensorflow_distributed_tpu.serve.transport import (
    TransportError,
    can_accept,
    migrate_request,
    pool_signature,
)


_KW = dict(num_slots=2, block_size=4, num_blocks=40, prefill_chunk=8,
           max_model_len=64, gather_buckets=[16, 32])


def _engine(model, params, **over):
    from huggingface_sagemaker_tensorflow_distributed_tpu.serve.engine import (
        ServeEngine,
    )

    kw = dict(_KW)
    kw.update(over)
    return ServeEngine(model, params, **kw)


def _slot_of(eng, rid):
    return next((s for s in eng.sched.slots
                 if s.request is not None and s.request.rid == rid), None)


def _conserved(eng):
    b = eng.blocks
    return (b.num_free + b.num_used + b.num_cached + b.num_hosted
            == b.num_blocks - 1)


def _baseline(model, params, trace, **over):
    eng = _engine(model, params, **over)
    reqs = [eng.submit(p, m, **kw) for p, m, kw in trace]
    eng.run()
    return [list(eng.output_ids(r)) for r in reqs]


def test_migrate_mid_decode_across_bucket_boundary_token_exact(
        gpt2_setup):
    """The core exactness contract: a request migrated MID-DECODE —
    after its context crossed the first gather bucket (16), so the
    destination resumes in the wider bucket — emits exactly the tokens
    an unmigrated engine emits, with zero re-prefill on the
    destination (its prefill counters stay at 0)."""
    _cfg, model, params = gpt2_setup
    rng = np.random.RandomState(0)
    prompt = rng.randint(1, 120, (14,)).astype(np.int32)
    base = _baseline(model, params, [(prompt, 12, {})])

    src = _engine(model, params)
    dst = _engine(model, params)
    req = src.submit(prompt, 12)
    while src.has_work():
        slot = _slot_of(src, req.rid)
        if slot is not None and slot.context_len > 18:
            break
        src.step()
    assert _slot_of(src, req.rid).context_len > 16   # bucket crossed
    info = migrate_request(src, dst, req.rid)
    assert info is not None and not info["cold"]
    assert info["bytes"] > 0 and info["context_len"] > 16
    # source fully released, destination fully owns the request
    assert _slot_of(src, req.rid) is None
    assert not src.has_work()
    assert src.blocks.num_used == 0 and _conserved(src)
    dst.run()
    assert list(dst.output_ids(req)) == base[0]
    assert req.rid in dst.finished and req.rid not in src.finished
    assert dst.stats().prefill_chunks == 0           # zero re-prefill
    assert dst.stats().migrations_in == 1
    assert src.stats().migrations_out == 1
    assert _conserved(dst) and dst.blocks.num_used == 0


def test_migrate_sampled_stream_bitwise_identical(gpt2_setup):
    """Sampled exactness: token n's key folds (request seed, n) — a
    pure function migration cannot perturb — so the migrated stream is
    BITWISE the unmigrated one."""
    _cfg, model, params = gpt2_setup
    rng = np.random.RandomState(2)
    prompt = rng.randint(1, 120, (9,)).astype(np.int32)
    skw = dict(temperature=0.9, top_k=20, seed=13)
    base = _baseline(model, params, [(prompt, 10, skw)])

    src = _engine(model, params)
    dst = _engine(model, params)
    req = src.submit(prompt, 10, **skw)
    while src.has_work() and len(req.output) < 4:
        src.step()
    assert len(req.output) >= 1                      # mid-decode
    assert migrate_request(src, dst, req.rid) is not None
    dst.run()
    assert list(dst.output_ids(req)) == base[0]


def test_migrate_rejections_and_signature(gpt2_setup):
    """The transport refuses loudly instead of corrupting state:
    self-moves, unknown rids, and geometry-incompatible pools (the
    block-set signature check) are all errors; an over-small
    destination fails ``can_accept``."""
    _cfg, model, params = gpt2_setup
    src = _engine(model, params)
    dst = _engine(model, params)
    assert pool_signature(src) == pool_signature(dst)
    req = src.submit(np.arange(1, 9, dtype=np.int32), 4)
    with pytest.raises(TransportError):
        migrate_request(src, src, req.rid)
    with pytest.raises(TransportError):
        migrate_request(src, dst, 10 ** 9)           # never submitted
    # different block_size => different pool geometry => refused
    other = _engine(model, params, block_size=8, num_blocks=20)
    assert pool_signature(src) != pool_signature(other)
    with pytest.raises(TransportError):
        migrate_request(src, other, req.rid)
    # a destination too small for the request's worst case
    tiny = _engine(model, params, num_blocks=4)
    assert not can_accept(tiny, req)
    with pytest.raises(TransportError):
        migrate_request(src, tiny, req.rid)
    src.run()
    # a finished request is a no-op, not an error
    assert migrate_request(src, dst, req.rid) is None


def test_randomized_two_engine_conservation_schedule(gpt2_setup):
    """The ISSUE 18 conservation property: 300 random
    submit/step/migrate operations across two engines (tight pools, so
    preemption pressure arises naturally) keep EVERY step's block
    accounting exact on BOTH pools (free + used + cached + hosted ==
    allocatable), every slot table points into its own pool, every
    request finishes exactly once somewhere, and the final outputs are
    token-identical to a single-engine run of the same trace."""
    _cfg, model, params = gpt2_setup
    rng = np.random.RandomState(7)
    kw = dict(num_blocks=14)
    engines = [_engine(model, params, **kw), _engine(model, params, **kw)]
    trace, reqs, homes = [], [], []
    migrations = refusals = 0
    for _ in range(300):
        op = rng.rand()
        if op < 0.35 and len(reqs) < 20:
            p = rng.randint(1, 120, (int(rng.randint(4, 12)),))
            m = int(rng.randint(2, 9))
            e = int(rng.randint(2))
            trace.append((p.astype(np.int32), m, {}))
            reqs.append(engines[e].submit(p.astype(np.int32), m))
            homes.append(e)
        elif op < 0.55:
            # migrate a random live resident to the other engine
            e = int(rng.randint(2))
            resident = [s.request.rid for s in engines[e].sched.slots
                        if s.request is not None]
            if resident:
                rid = int(rng.choice(resident))
                try:
                    if migrate_request(engines[e], engines[1 - e],
                                       rid) is not None:
                        migrations += 1
                        homes[[q.rid for q in reqs].index(rid)] = 1 - e
                except TransportError:
                    refusals += 1    # e.g. destination worst-case full
        else:
            e = int(rng.randint(2))
            if engines[e].has_work():
                engines[e].step()
        for eng in engines:
            assert _conserved(eng)
            for s in eng.sched.slots:
                if s.request is not None:
                    n = eng.blocks.blocks_for(s.context_len)
                    assert all(0 < int(b) < eng.blocks.num_blocks
                               for b in s.table[:n])
    for eng in engines:
        eng.run()
    assert migrations > 0
    finished = [set(e.finished) for e in engines]
    assert not (finished[0] & finished[1])           # exactly-once
    assert finished[0] | finished[1] == {q.rid for q in reqs}
    base = _baseline(model, params, trace, **kw)
    outs = [list(engines[homes[i]].output_ids(q))
            for i, q in enumerate(reqs)]
    assert outs == base
    for eng in engines:
        assert eng.blocks.num_used == 0 and _conserved(eng)


def test_drain_live_migrates_residents_and_completes(gpt2_setup,
                                                     tmp_path):
    """With transport under it, ``Router.drain`` empties the replica
    IMMEDIATELY: waiting requests requeue, residents live-migrate
    mid-flight (no waiting them out), the drain event carries the
    structured migrated/residents_in_place split, migrate events carry
    the byte/latency accounting, and the run stays token-identical."""
    _cfg, model, params = gpt2_setup
    rng = np.random.RandomState(1)
    trace = [(rng.randint(1, 120, (int(rng.randint(5, 13)),))
              .astype(np.int32), int(rng.randint(3, 9)), {})
             for _ in range(8)]
    base = _baseline(model, params, trace)

    out = tmp_path / "drain"
    obs.reset(out_dir=str(out), enabled=True)
    try:
        router = Router(model, params, replicas=2,
                        placement="round_robin", **_KW)
        reqs = [router.submit(p, m) for p, m, _ in trace]
        router.warmup()
        for _ in range(3):
            router.step()
        src = router.engines[0]
        had_residents = any(s.request is not None
                            for s in src.sched.slots)
        router.drain(0)
        # the drain completed NOW: nothing resident, nothing queued
        assert had_residents and router.migrations > 0
        assert all(s.request is None for s in src.sched.slots)
        assert not src.sched.waiting
        assert src.blocks.num_used == 0
        router.run()
        obs.flush()
    finally:
        obs.reset()
    assert [list(router.output_ids(q)) for q in reqs] == base
    assert len(router.finished) == len(trace)
    for eng in router.engines:
        assert eng.blocks.num_used == 0 and _conserved(eng)
    events = [e for _, e, err in obs.iter_events(
        str(out / "events.jsonl")) if err is None]
    drains = [e for e in events if e.get("event") == "drain"]
    assert len(drains) == 1
    assert drains[0]["migrated"] >= 1
    assert drains[0]["residents_in_place"] == 0
    migrates = [e for e in events if e.get("event") == "migrate"]
    assert len(migrates) == router.migrations
    for e in migrates:
        assert e["from_replica"] == 0 and e["to_replica"] == 1
        assert isinstance(e["migration_bytes"], int)
        assert isinstance(e["restore_s"], float)
    assert any(e["migration_bytes"] > 0 for e in migrates)


def test_disaggregated_roles_token_identical_and_separated(gpt2_setup):
    """The prefill/decode split end to end: token identity vs one
    engine, ZERO decode iterations on the prefill replica, zero
    submissions on the decode replica, every request handed over the
    transport exactly once, and the fleet summary's per-role
    attribution present."""
    _cfg, model, params = gpt2_setup
    rng = np.random.RandomState(4)
    trace = [(rng.randint(1, 120, (int(rng.randint(5, 13)),))
              .astype(np.int32), int(rng.randint(3, 9)), {})
             for _ in range(6)]
    base = _baseline(model, params, trace)
    router = Router(model, params, roles="prefill:1,decode:1", **_KW)
    reqs = [router.submit(p, m) for p, m, _ in trace]
    router.run()
    assert [list(router.output_ids(q)) for q in reqs] == base
    assert router.role_of == ["prefill", "decode"]
    pre, dec = router.engines
    assert pre.stats().decode_steps == 0
    assert dec.stats().prefill_dispatches == 0
    assert router.migrations == len(trace)
    assert pre.stats().migrations_out == len(trace)
    assert dec.stats().migrations_in == len(trace)
    assert all(router.replica_of(q) == 1 for q in reqs)
    slo = router.slo_summary()
    assert slo["roles"] == "prefill:1,decode:1"
    assert set(slo["per_role"]) == {"prefill", "decode"}
    assert slo["per_role"]["prefill"]["decode_steps"] == 0
    assert slo["migrations"] == len(trace)
    assert slo["migration_bytes"] > 0
    # an impossible request is refused at SUBMIT, not stuck mid-fleet
    with pytest.raises(ValueError):
        router.submit(rng.randint(1, 120, (60,)).astype(np.int32), 16)


def test_length_aware_heterogeneous_fleet(gpt2_setup):
    """Heterogeneous fleets: per-replica overrides build a small and a
    large replica (same pool signature — transport-compatible), and
    length-aware placement sends long prompts to the deep class, short
    ones to the shallow class, token-identically."""
    _cfg, model, params = gpt2_setup
    rng = np.random.RandomState(6)
    short = [(rng.randint(1, 120, (5,)).astype(np.int32), 4, {})
             for _ in range(2)]
    long_ = [(rng.randint(1, 120, (16,)).astype(np.int32), 4, {})
             for _ in range(2)]
    trace = [row for pair in zip(short, long_) for row in pair]
    base = _baseline(model, params, trace)
    router = Router(model, params, replicas=2, placement="length_aware",
                    replica_kwargs=[{"num_blocks": 20}, {}],
                    length_threshold=10, **_KW)
    assert (router.engines[0].blocks.num_blocks
            < router.engines[1].blocks.num_blocks)
    reqs = [router.submit(p, m) for p, m, _ in trace]
    owners = [router.replica_of(q) for q in reqs]
    assert owners == [0, 1, 0, 1]     # short -> shallow, long -> deep
    router.run()
    assert [list(router.output_ids(q)) for q in reqs] == base


def test_parse_roles_knob(monkeypatch):
    assert parse_roles(None) is None
    assert parse_roles("") is None
    assert parse_roles("prefill:1,decode:2") == {"prefill": 1,
                                                 "decode": 2}
    assert parse_roles({"prefill": 2, "decode": 1}) == {"prefill": 2,
                                                        "decode": 1}
    monkeypatch.setenv("HSTD_SERVE_ROLES", "prefill:1,decode:1")
    assert parse_roles(None) == {"prefill": 1, "decode": 1}
    for bad in ("prefill:1", "decode:2", "prefill:0,decode:1",
                "verify:1,decode:1", "prefill=1,decode=1",
                "prefill:x,decode:1"):
        with pytest.raises(ValueError):
            parse_roles(bad)


def test_roles_contradicting_replicas_refused(gpt2_setup):
    _cfg, model, params = gpt2_setup
    with pytest.raises(ValueError):
        Router(model, params, replicas=3, roles="prefill:1,decode:1",
               **_KW)
    # matching counts are fine
    r = Router(model, params, replicas=2, roles="prefill:1,decode:1",
               **_KW)
    assert r.n == 2 and r.roles == {"prefill": 1, "decode": 1}
