"""Goodput-aware admission control (ISSUE 20): the policy layer's
contracts, host-side and end-to-end.

- knob parsing (`HSTD_SERVE_POLICY` / `HSTD_SERVE_AGING_S`), the
  token-bucket rate limiter, and the `group=rate[:burst]` spec grammar;
- the slo admission key: priority dominates deadline dominates
  predicted demand (prefix-cache-aware), with the aging tier promoted
  ahead of everything and FIFO among itself;
- the property test: a seeded 300-step submit/admit/preempt/finish
  schedule under ``policy=slo`` holding the aging bound (nothing
  younger admits past a starving request), block conservation, and
  no starvation (everything finishes, token counts exact);
- the byte-identity contract: a ``policy="fifo"`` engine's serve-event
  stream is structurally identical to a default-built engine's, with
  ZERO ISSUE-20 fields present — and the schema validator rejects
  mistyped rider rows;
- the router's structured per-tenant rejection: an empty bucket
  returns :class:`RateLimited` (counted, ``retry_after_s`` named),
  never a silent drop.
"""

import types

import numpy as np
import pytest

from huggingface_sagemaker_tensorflow_distributed_tpu import obs
from huggingface_sagemaker_tensorflow_distributed_tpu.obs.schema import (
    validate_event,
)
from huggingface_sagemaker_tensorflow_distributed_tpu.serve.paged_kv import (
    BlockManager,
)
from huggingface_sagemaker_tensorflow_distributed_tpu.serve.policy import (
    DEFAULT_AGING_S,
    ENV_AGING_S,
    ENV_POLICY,
    POLICIES,
    RateLimited,
    SloPolicy,
    TokenBucket,
    parse_aging_s,
    parse_policy,
    parse_rate_limit,
    request_origin,
)
from huggingface_sagemaker_tensorflow_distributed_tpu.serve.scheduler import (
    DECODE,
    FINISHED,
    PREFILL,
    WAITING,
    Request,
    Scheduler,
)

# -- knob parsing ------------------------------------------------------------


def test_parse_policy_default_env_and_errors(monkeypatch):
    monkeypatch.delenv(ENV_POLICY, raising=False)
    assert parse_policy(None) == "fifo"
    monkeypatch.setenv(ENV_POLICY, "slo")
    assert parse_policy(None) == "slo"
    monkeypatch.setenv(ENV_POLICY, "")
    assert parse_policy(None) == "fifo"
    assert parse_policy(" SLO ") == "slo"
    with pytest.raises(ValueError, match=ENV_POLICY):
        parse_policy("edf")
    assert POLICIES == ("fifo", "slo")


def test_parse_aging_default_env_and_errors(monkeypatch):
    monkeypatch.delenv(ENV_AGING_S, raising=False)
    assert parse_aging_s(None) == DEFAULT_AGING_S
    monkeypatch.setenv(ENV_AGING_S, "2.5")
    assert parse_aging_s(None) == 2.5
    assert parse_aging_s(" 7 ") == 7.0
    for bad in ("soon", "0", "-3", "inf", "nan"):
        with pytest.raises(ValueError, match=ENV_AGING_S):
            parse_aging_s(bad)


def test_scheduler_reads_policy_env(monkeypatch):
    monkeypatch.setenv(ENV_POLICY, "slo")
    monkeypatch.setenv(ENV_AGING_S, "2.5")
    s = Scheduler(1, BlockManager(5, 4), 4, 16)
    assert s.policy == "slo" and s.aging_s == 2.5
    assert isinstance(s._policy, SloPolicy)
    monkeypatch.delenv(ENV_POLICY)
    monkeypatch.delenv(ENV_AGING_S)
    # the default scheduler is the pre-ISSUE-20 one: no policy object
    # at all, so the fifo admit path runs bit-for-bit
    s = Scheduler(1, BlockManager(5, 4), 4, 16)
    assert s.policy == "fifo" and s._policy is None


# -- token bucket + rate-limit grammar ---------------------------------------


def test_token_bucket_refill_burst_and_backwards_clock():
    b = TokenBucket(rate=1.0, burst=2.0)
    assert b.try_take(0.0) == (True, 0.0)
    assert b.try_take(0.0) == (True, 0.0)
    ok, retry = b.try_take(0.0)
    assert not ok and retry == pytest.approx(1.0)
    # lazy refill from the last observed clock; the cap holds
    ok, _ = b.try_take(1.0)
    assert ok
    ok, retry = b.try_take(1.0)
    assert not ok and retry == pytest.approx(1.0)
    # a clock that goes backwards refills nothing and never raises
    ok, retry = b.try_take(0.5)
    assert not ok and retry == pytest.approx(1.0)
    with pytest.raises(ValueError, match="rate"):
        TokenBucket(0.0, 2.0)
    with pytest.raises(ValueError, match="burst"):
        TokenBucket(1.0, 0.5)
    with pytest.raises(ValueError, match="rate"):
        TokenBucket(float("inf"), 2.0)


def test_parse_rate_limit_dict_string_and_errors():
    assert parse_rate_limit(None) == {}
    assert parse_rate_limit("") == {}
    assert parse_rate_limit({"a": (2.0, 4.0)}) == {"a": (2.0, 4.0)}
    # scalar rate: burst defaults to max(1, rate)
    assert parse_rate_limit({"a": 3}) == {"a": (3.0, 3.0)}
    assert parse_rate_limit({"a": 0.5}) == {"a": (0.5, 1.0)}
    assert parse_rate_limit("a=2:4, b=3 ,*=0.5") == {
        "a": (2.0, 4.0), "b": (3.0, 3.0), "*": (0.5, 1.0)}
    with pytest.raises(ValueError, match="group=rate"):
        parse_rate_limit("nope")
    with pytest.raises(ValueError, match="rate"):
        parse_rate_limit("a=0")
    with pytest.raises(ValueError, match="burst"):
        parse_rate_limit("a=2:0")


def test_rate_limited_is_structured_and_frozen():
    r = RateLimited(group="t0", retry_after_s=0.25, rate=2.0, burst=4.0)
    assert r.rejected is True
    assert not getattr(Request(prompt=[1], max_new_tokens=1),
                       "rejected", False)
    with pytest.raises(Exception):
        r.group = "other"


# -- the slo admission key ---------------------------------------------------


def _req(prompt_len=4, max_new=4, **kw):
    return Request(prompt=np.arange(1, prompt_len + 1),
                   max_new_tokens=max_new, **kw)


def test_request_origin_prefers_arrival_over_submit():
    r = _req()
    assert request_origin(r) == 0.0
    r.submit_t = 5.0
    assert request_origin(r) == 5.0
    r.arrival_s = 3.0
    assert request_origin(r) == 3.0


def test_slo_key_priority_deadline_demand_rid_order():
    pol = SloPolicy(aging_s=100.0)
    demand = {"urgent": 2, "soon": 2, "later": 2, "small": 1, "big": 3}
    reqs = {}
    for name, (prio, dl) in {
            "urgent": (0, 1.0), "soon": (1, 1.0), "later": (1, 5.0),
            "small": (1, None), "big": (1, None)}.items():
        r = _req(deadline_s=dl, priority=prio)
        r.arrival_s = 0.0
        reqs[name] = r
    names = {r.rid: n for n, r in reqs.items()}
    ranked = pol.rank(list(reqs.values())[::-1], now=0.0,
                      demand_blocks=lambda r: demand[names[r.rid]])
    order = [names[r.rid] for r in ranked]
    # priority class first, then effective deadline (deadline-less
    # last), then predicted demand, then rid
    assert order == ["urgent", "soon", "later", "small", "big"]
    # same priority/deadline/demand: rid (submission order) breaks ties
    a, b = _req(deadline_s=1.0), _req(deadline_s=1.0)
    a.arrival_s = b.arrival_s = 0.0
    assert pol.rank([b, a], 0.0, lambda r: 1) == [a, b]


def test_aging_tier_promotes_fifo_by_origin_ahead_of_priorities():
    pol = SloPolicy(aging_s=10.0)
    old_lo = _req(priority=5)           # worst class, but starving
    old_lo.arrival_s = 0.0
    older_lo = _req(priority=9)
    older_lo.arrival_s = -1.0
    fresh_hi = _req(priority=0, deadline_s=0.1)
    fresh_hi.arrival_s = 95.0
    ranked = pol.rank([fresh_hi, old_lo, older_lo], now=100.0,
                      demand_blocks=lambda r: 1)
    # both aged requests jump the urgent fresh one; FIFO among
    # themselves by origin, priority ignored inside the tier
    assert ranked == [older_lo, old_lo, fresh_hi]
    assert pol.promoted(old_lo, 100.0)
    assert not pol.promoted(fresh_hi, 100.0)


def test_demand_blocks_is_cache_aware_and_swap_exact():
    bm = BlockManager(num_blocks=12, block_size=4)
    s = Scheduler(2, bm, 4, 32, policy="slo", prefix_cache=True)
    table = bm.allocate(2)
    bm.register_prefix(np.arange(1, 9), table)
    bm.release(table)                   # cached, zero-ref
    cold = Request(prompt=np.arange(50, 62), max_new_tokens=4)
    warm = Request(prompt=np.concatenate([np.arange(1, 9),
                                          np.array([90, 91, 92, 93])]),
                   max_new_tokens=4)
    assert s._demand_blocks(cold) == 3
    assert s._demand_blocks(warm) == 1  # 2 of 3 blocks served cached
    # the probe is refcount/LRU-neutral: still fully free capacity
    assert bm.num_free + bm.num_cached == bm.num_blocks - 1
    swapped = _req()
    swapped.swap_set = types.SimpleNamespace(n_blocks=5)
    assert s._demand_blocks(swapped) == 5


# -- scheduler-level admission order ----------------------------------------


def _slo_sched(num_slots=2, num_blocks=9, block_size=4, chunk=4,
               max_len=32, aging_s=100.0, **kw):
    return Scheduler(num_slots, BlockManager(num_blocks, block_size),
                     chunk, max_len, policy="slo", aging_s=aging_s, **kw)


def test_slo_admission_orders_by_deadline_not_arrival():
    s = _slo_sched()
    s.policy_now = 10.0
    late = _req(deadline_s=50.0)
    late.arrival_s = 0.0
    mid = _req(deadline_s=20.0)
    mid.arrival_s = 1.0
    tight = _req(deadline_s=5.0)
    tight.arrival_s = 2.0
    for r in (late, mid, tight):
        s.submit(r)
    admitted = s.admit()
    # two slots: the two tightest effective deadlines win, FIFO would
    # have taken (late, mid)
    assert [sl.request is r for sl, r in zip(admitted, (tight, mid))] \
        == [True, True]
    assert late.state == WAITING


def test_smaller_demand_fills_slot_the_frontrunner_cannot():
    # pool: 4 allocatable blocks; resident request holds 2
    s = _slo_sched(num_slots=3, num_blocks=5)
    s.policy_now = 0.0
    resident = _req(prompt_len=8, max_new=4)
    resident.arrival_s = 0.0
    s.submit(resident)
    assert len(s.admit()) == 1
    big = _req(prompt_len=12, max_new=1, deadline_s=1.0)   # needs 3
    big.arrival_s = 0.0
    small = _req(prompt_len=4, max_new=4, deadline_s=9.0)  # needs 1
    small.arrival_s = 0.0
    s.submit(big)
    s.submit(small)
    admitted = s.admit()
    # big ranks first but cannot fit (2 blocks free); slo lets the
    # smaller-demand candidate take the slot — fifo would head-block
    assert [sl.request is small for sl in admitted] == [True]
    assert big.state == WAITING and small.state == PREFILL


def test_aging_promoted_request_blocks_all_younger_admission():
    s = _slo_sched(num_slots=3, num_blocks=5, aging_s=10.0)
    s.policy_now = 0.0
    resident = _req(prompt_len=8, max_new=4)
    resident.arrival_s = 0.0
    s.submit(resident)
    assert len(s.admit()) == 1
    big = _req(prompt_len=12, max_new=1)   # needs 3 > 2 free
    big.arrival_s = 0.0
    small = _req(prompt_len=4, max_new=4, deadline_s=1.0)
    small.arrival_s = 11.0
    s.submit(big)
    s.submit(small)
    s.policy_now = 11.0                    # big has now starved 11s
    assert s.admit() == []                 # strict bound: NOBODY passes
    assert big.aging_promoted and s.aging_promotions == 1
    assert small.state == WAITING
    assert s.blocked_head() is big
    # promotion is counted once, and admission resumes the moment the
    # starving request fits: free the resident's pool share
    s.finish(s.slots[0])
    order = [sl.request for sl in s.admit()]
    assert order == [big, small]
    assert s.aging_promotions == 1


# -- the property test -------------------------------------------------------


def _conserved(bm):
    return (bm.num_free + bm.num_used + bm.num_cached + bm.num_hosted
            == bm.num_blocks - 1)


def _step_host_engine(s, rng=None, preempt_p=0.0):
    """One engine iteration, host-side: admit, instant prefill, decode
    one token per slot, finish at max_new — the scheduler's own
    contract surface, no jax. Returns the slots admitted this call."""
    admitted = s.admit()
    for slot in s.slots:
        if slot.request is not None and slot.request.state == PREFILL:
            s.finish_prefill(slot)
    if rng is not None and preempt_p and rng.rand() < preempt_p:
        busy = [sl for sl in s.slots
                if sl.request is not None and sl.request.state == DECODE]
        if busy:
            s.preempt(busy[rng.randint(len(busy))])
    s.ensure_decode_capacity()
    for slot in s.slots:
        req = slot.request
        if req is None or req.state != DECODE:
            continue
        slot.context_len += 1
        req.output.append(1)
        done = (len(req.prompt) - req.orig_prompt_len
                + len(req.output)) >= req.max_new_tokens
        if done:
            s.finish(slot)
    return admitted


def test_slo_schedule_property_300_steps():
    """Randomized 300-step schedule under ``policy=slo``: submits,
    admissions, natural + injected preemptions, finishes — asserting
    after EVERY step (a) the aging bound: while a promoted (starving)
    request waits, no un-promoted request is admitted past it;
    (b) block conservation; and at the end (c) no starvation: every
    request finishes with its exact token count, pool drained."""
    rng = np.random.RandomState(0)
    s = _slo_sched(num_slots=3, num_blocks=13, block_size=4, chunk=4,
                   max_len=32, aging_s=0.6)
    t = 0.0
    everyone = []
    for step in range(300):
        t += 0.05
        s.policy_now = t
        if len(everyone) < 60 and rng.rand() < 0.35:
            r = Request(
                prompt=rng.randint(1, 100, (rng.randint(1, 13),)),
                max_new_tokens=int(rng.randint(1, 9)),
                priority=int(rng.randint(0, 3)),
                deadline_s=(float(rng.uniform(0.2, 5.0))
                            if rng.rand() < 0.7 else None))
            r.arrival_s = t
            s.submit(r)
            everyone.append(r)
        admitted = _step_host_engine(s, rng, preempt_p=0.05)
        if any(r.aging_promoted for r in s.waiting):
            assert all(sl.request.aging_promoted for sl in admitted), \
                f"step {step}: younger work queue-jumped a starving " \
                "request"
        assert _conserved(s.blocks), f"step {step}: blocks leaked"
    # drain: no new work, everything must complete (liveness)
    for step in range(2000):
        if not s.has_work():
            break
        t += 0.05
        s.policy_now = t
        _step_host_engine(s)
        assert _conserved(s.blocks)
    assert not s.has_work(), "schedule never drained: starvation"
    assert everyone and all(r.state == FINISHED for r in everyone)
    for r in everyone:
        got = len(r.prompt) - r.orig_prompt_len + len(r.output)
        assert got == r.max_new_tokens, \
            f"request {r.rid}: {got} tokens != {r.max_new_tokens}"
    assert s.blocks.num_used == 0
    assert s.aging_promotions == sum(
        1 for r in everyone if r.aging_promoted)


# -- schema: typed riders, mistyped rows rejected ----------------------------


def test_schema_types_policy_riders_and_rejects_mistypes():
    base = {"v": 1, "t": 1.0, "host": 0, "pid": 1, "type": "serve",
            "event": "finish", "request": 3, "deadline_s": 0.5,
            "priority": 1, "deadline_miss": False}
    assert validate_event(base) == []
    limited = {"v": 1, "t": 1.0, "host": 0, "pid": 1, "type": "serve",
               "event": "rate_limited", "group": "t0",
               "retry_after_s": 0.25, "rate_limited": 2}
    assert validate_event(limited) == []
    report = dict(base, event="report", policy="slo",
                  aging_promotions=4, deadline_miss_frac=0.25,
                  priority_slo_attainment={"0": 1.0, "1": 0.5})
    assert validate_event(report) == []
    for field, bad in [("deadline_s", "soon"), ("priority", 1.5),
                       ("priority", True), ("deadline_miss", "no"),
                       ("rate_limited", 0.5), ("retry_after_s", "later"),
                       ("policy", 7), ("aging_promotions", "many"),
                       ("deadline_miss_frac", "low"),
                       ("priority_slo_attainment", [1.0])]:
        row = dict(report, **{field: bad})
        errs = validate_event(row)
        assert errs and field in errs[0], (field, bad, errs)


# -- engine + router end-to-end (jax) ----------------------------------------


_ENGINE_KW = dict(num_slots=2, block_size=4, num_blocks=20,
                  prefill_chunk=8, max_model_len=64)

_POLICY_FIELDS = {"policy", "deadline_s", "priority", "deadline_miss",
                  "rate_limited", "retry_after_s", "aging_promotions",
                  "deadline_miss_frac", "priority_slo_attainment"}


def _serve_events(model, params, trace, out_dir, **engine_kw):
    from huggingface_sagemaker_tensorflow_distributed_tpu.serve.engine import (
        ServeEngine,
    )

    obs.reset(out_dir=str(out_dir), enabled=True)
    try:
        eng = ServeEngine(model, params, **engine_kw)
        reqs = [eng.submit(p, m) for p, m in trace]
        eng.run()
        outs = [list(eng.output_ids(r)) for r in reqs]
        summary = eng.slo_summary()
        obs.flush()
    finally:
        obs.reset()
    events = [e for _, e, err in
              obs.iter_events(str(out_dir / "events.jsonl"))
              if err is None and e["type"] == "serve"]
    return events, outs, summary


def test_fifo_event_stream_identical_to_default_engine(gpt2_setup,
                                                       tmp_path):
    """The byte-identity contract: ``policy="fifo"`` IS the pre-ISSUE
    -20 engine. Same trace through a default-built engine and an
    explicit fifo one → the serve-event streams carry the same events
    with the same field sets in the same order, token-identical
    outputs, and ZERO ISSUE-20 fields anywhere (events or summary)."""
    _cfg, model, params = gpt2_setup
    rng = np.random.RandomState(7)
    trace = [(rng.randint(1, 120, (p,)).astype(np.int32), m)
             for p, m in [(5, 6), (11, 4), (7, 8), (4, 5)]]
    ev_a, outs_a, sum_a = _serve_events(model, params, trace,
                                        tmp_path / "default",
                                        **_ENGINE_KW)
    ev_b, outs_b, sum_b = _serve_events(model, params, trace,
                                        tmp_path / "fifo",
                                        policy="fifo", **_ENGINE_KW)
    shape_a = [(e["event"], tuple(sorted(set(e) - {"request", "t"})))
               for e in ev_a]
    shape_b = [(e["event"], tuple(sorted(set(e) - {"request", "t"})))
               for e in ev_b]
    assert shape_a == shape_b
    assert outs_a == outs_b
    for events, summary in ((ev_a, sum_a), (ev_b, sum_b)):
        hit = [k for e in events for k in e if k in _POLICY_FIELDS]
        assert not hit, f"fifo stream leaked policy fields: {hit}"
        assert not (_POLICY_FIELDS & set(summary))


def test_slo_engine_emits_riders_and_valid_events(gpt2_setup, tmp_path):
    """policy=slo with deadlines/priorities: tokens still identical to
    fifo (the WHO-not-WHAT contract), finish events carry the
    deadline verdicts, the summary carries the gated rollups, and the
    whole stream passes the schema validator."""
    from huggingface_sagemaker_tensorflow_distributed_tpu.serve.engine import (
        ServeEngine,
    )
    from huggingface_sagemaker_tensorflow_distributed_tpu.serve.loadgen import (
        SloSpec,
    )

    _cfg, model, params = gpt2_setup
    rng = np.random.RandomState(9)
    trace = [(rng.randint(1, 120, (p,)).astype(np.int32), m)
             for p, m in [(6, 5), (9, 6), (5, 4), (12, 7)]]
    base_ev, base_outs, _ = _serve_events(model, params, trace,
                                          tmp_path / "base", **_ENGINE_KW)
    out = tmp_path / "slo"
    obs.reset(out_dir=str(out), enabled=True)
    try:
        eng = ServeEngine(model, params, policy="slo", aging_s=60.0,
                          **_ENGINE_KW)
        slo = SloSpec(ttft_s=30.0)
        reqs = [eng.submit(p, m, deadline_s=(1e-9 if i % 2 else 1e6),
                           priority=i % 2, slo=slo)
                for i, (p, m) in enumerate(trace)]
        eng.run()
        outs = [list(eng.output_ids(r)) for r in reqs]
        summary = eng.slo_summary()
        obs.flush()
    finally:
        obs.reset()
    assert sorted(map(tuple, outs)) == sorted(map(tuple, base_outs))
    assert summary["policy"] == "slo"
    assert summary["deadline_miss_frac"] == 0.5
    assert set(summary["priority_slo_attainment"]) == {"0", "1"}
    assert [r.deadline_miss for r in reqs] == [False, True] * 2
    count, errors = obs.validate_events_file(str(out / "events.jsonl"))
    assert not errors and count > 0
    serve_ev = [e for _, e, err in
                obs.iter_events(str(out / "events.jsonl"))
                if err is None and e["type"] == "serve"]
    submits = [e for e in serve_ev if e.get("event") == "submit"]
    finishes = [e for e in serve_ev if e.get("event") == "finish"]
    assert len(finishes) == len(trace)
    # deadline_s rides the submit event, the verdict rides finish
    assert all("deadline_s" in e for e in submits)
    assert all("deadline_miss" in e for e in finishes)
    assert sum(e.get("priority", 0) for e in submits) == 2


def test_router_rate_limit_structured_rejection(gpt2_setup, tmp_path):
    """An empty tenant bucket rejects STRUCTURALLY: the submit returns
    :class:`RateLimited` with the bucket's own retry estimate, the
    rejection is counted in the fleet summary, and un-metered groups
    pass untouched — never a silent drop, never an exception."""
    from huggingface_sagemaker_tensorflow_distributed_tpu.serve.router import (
        Router,
    )

    _cfg, model, params = gpt2_setup
    rng = np.random.RandomState(11)
    out = tmp_path / "router"
    obs.reset(out_dir=str(out), enabled=True)
    try:
        router = Router(model, params, replicas=1,
                        rate_limit={"metered": (0.5, 2)}, **_ENGINE_KW)
        reqs = [router.submit(rng.randint(1, 120, (5,)).astype(np.int32),
                              4, group="metered", arrival_s=0.0)
                for _ in range(4)]
        free = router.submit(rng.randint(1, 120, (5,)).astype(np.int32),
                             4, group="unmetered", arrival_s=0.0)
        router.run()
        summary = router.slo_summary()
        obs.flush()
    finally:
        obs.reset()
    limited = [r for r in reqs if getattr(r, "rejected", False)]
    served = [r for r in reqs if not getattr(r, "rejected", False)]
    assert len(limited) == 2 and len(served) == 2   # burst=2
    assert all(isinstance(r, RateLimited) for r in limited)
    # virtual clock pinned at 0: retry = one token at 0.5 tok/s
    assert all(r.retry_after_s == pytest.approx(2.0) for r in limited)
    assert all(r.group == "metered" for r in limited)
    assert not getattr(free, "rejected", False)
    assert summary["rate_limited"] == 2
    assert all(r.state == FINISHED for r in served + [free])
    events = [e for _, e, err in
              obs.iter_events(str(out / "events.jsonl"))
              if err is None and e["type"] == "serve"
              and e.get("event") == "rate_limited"]
    assert len(events) == 2
    assert all(e["group"] == "metered" and e["retry_after_s"] > 0
               for e in events)
    count, errors = obs.validate_events_file(str(out / "events.jsonl"))
    assert not errors
