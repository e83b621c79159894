"""serve/ subsystem: block-manager accounting, iteration-level
scheduler policy, and the engine exactness gate — continuous-batched
greedy decode must be token-for-token identical to per-request
``generate_causal`` (with and without preemption), for both the GPT-2
and Llama/GQA cache layouts."""

import numpy as np
import pytest

from huggingface_sagemaker_tensorflow_distributed_tpu import obs
from huggingface_sagemaker_tensorflow_distributed_tpu.serve.paged_kv import (
    BlockManager,
    PoolExhausted,
)
from huggingface_sagemaker_tensorflow_distributed_tpu.serve.scheduler import (
    DECODE,
    PREFILL,
    WAITING,
    Request,
    Scheduler,
)


# -- block manager (pure host) -----------------------------------------------

def test_block_alloc_free_roundtrip():
    bm = BlockManager(num_blocks=9, block_size=4)
    assert bm.num_free == 8                        # block 0 reserved
    got = bm.allocate(3)
    assert len(got) == 3 and 0 not in got
    assert bm.num_used == 3 and bm.peak_used == 3
    bm.free(got)
    assert bm.num_free == 8 and bm.peak_used == 3  # peak latches
    with pytest.raises(ValueError):
        bm.free([got[0], got[0]])                  # double free
    with pytest.raises(ValueError):
        bm.free([0])                               # the null block


def test_pool_exhausted_is_all_or_nothing():
    bm = BlockManager(num_blocks=5, block_size=4)
    bm.allocate(2)
    with pytest.raises(PoolExhausted):
        bm.allocate(3)
    assert bm.num_free == 2                        # nothing leaked


def test_grow_and_trim_follow_context():
    bm = BlockManager(num_blocks=9, block_size=4)
    table = []
    assert len(bm.grow(table, 1)) == 1             # 1 token -> 1 block
    assert bm.grow(table, 4) == []                 # still fits
    assert len(bm.grow(table, 5)) == 1             # crosses the boundary
    assert len(table) == 2
    bm.trim(table, 3)                              # back to 1 block
    assert len(table) == 1 and bm.num_free == 7


def test_fragmentation_is_last_block_padding():
    bm = BlockManager(num_blocks=9, block_size=4)
    # contexts 5 and 8: held slots 8 + 8, used 13 -> 3/16 wasted
    assert bm.fragmentation([5, 8]) == pytest.approx(3 / 16)
    assert bm.fragmentation([]) == 0.0


# -- scheduler (pure host) ---------------------------------------------------

def _sched(num_slots=2, num_blocks=9, block_size=4, chunk=4, max_len=32):
    return Scheduler(num_slots, BlockManager(num_blocks, block_size),
                     chunk, max_len)


def test_admission_is_fifo_into_free_slots():
    s = _sched()
    reqs = [Request(prompt=np.arange(1, 4), max_new_tokens=4)
            for _ in range(3)]
    for r in reqs:
        s.submit(r)
    admitted = s.admit()
    assert [sl.request.rid for sl in admitted] == [reqs[0].rid, reqs[1].rid]
    assert reqs[0].state == PREFILL and reqs[2].state == WAITING
    # padded-prompt reservation: 3 tokens pad to chunk 4 -> 1 block each
    assert s.blocks.num_used == 2
    assert s.admit() == []                         # no free slot


def test_admission_respects_pool_capacity():
    s = _sched(num_slots=2, num_blocks=4)          # 3 allocatable blocks
    a = Request(prompt=np.arange(1, 9), max_new_tokens=4)   # pad 8 -> 2 blocks
    b = Request(prompt=np.arange(1, 9), max_new_tokens=4)
    s.submit(a)
    s.submit(b)
    assert [sl.request.rid for sl in s.admit()] == [a.rid]
    assert b.state == WAITING                      # FIFO: b never jumps


def test_submit_rejects_over_length_requests():
    s = _sched(max_len=16)
    with pytest.raises(ValueError):
        s.submit(Request(prompt=np.arange(1, 14), max_new_tokens=8))


def test_submit_rejects_requests_that_can_never_fit_the_pool():
    """A request whose worst-case block need exceeds the WHOLE pool
    would otherwise livelock the engine: admit() parks it at the queue
    head forever (or a lone decode slot preempts itself in a loop)."""
    s = _sched(num_slots=1, num_blocks=4, block_size=4, max_len=32)
    with pytest.raises(ValueError, match="KV blocks"):
        s.submit(Request(prompt=np.arange(1, 9), max_new_tokens=12))
    # exactly at capacity is fine (3 blocks hold 12 tokens lifetime)
    s.submit(Request(prompt=np.arange(1, 9), max_new_tokens=4))


def test_scheduler_rejects_chunk_not_dividing_max_model_len():
    """padded_prompt_len must never exceed max_model_len (block tables
    are sized for it) — enforced by requiring the chunk to divide it."""
    with pytest.raises(ValueError, match="prefill_chunk"):
        _sched(chunk=48, max_len=64)


def test_preemption_evicts_youngest_and_requeues_front():
    s = _sched(num_slots=2, num_blocks=6, block_size=4, chunk=4)
    old = Request(prompt=np.arange(1, 5), max_new_tokens=16)
    young = Request(prompt=np.arange(1, 5), max_new_tokens=16)
    s.submit(old)
    s.submit(young)
    s.admit()
    for slot in s.slots:                            # fake finished prefill
        s.finish_prefill(slot)
        slot.request.output = [7, 8]
        slot.context_len = 6
    # 4 allocatable blocks, both slots at 2 blocks each once they cross
    # context 8; growing both is impossible -> youngest goes
    s.slots[0].context_len = s.slots[1].context_len = 8
    preempted = s.ensure_decode_capacity()
    assert [r.rid for r in preempted] == [young.rid]
    assert young.state == WAITING and s.waiting[0] is young
    # recompute style: generated tokens folded into the prompt
    assert list(young.prompt) == [1, 2, 3, 4, 7, 8]
    assert young.output == [] and young.preemptions == 1
    assert old.state == DECODE                     # survivor kept its slot


# -- paged addressing primitives (ops/attention.py) --------------------------

def test_paged_attention_matches_contiguous():
    """gather/scatter round-trip + paged_attention == xla_attention over
    the same contiguous KV — the addressing contract the engine's
    cache-assembly path is built on."""
    import jax.numpy as jnp

    from huggingface_sagemaker_tensorflow_distributed_tpu.ops.attention import (
        gather_paged_kv,
        paged_attention,
        scatter_paged_kv,
        xla_attention,
    )

    rng = np.random.RandomState(0)
    S, H, D, bs, nb_per = 3, 2, 4, 4, 3          # max_ctx = 12
    max_ctx = bs * nb_per
    ctx = np.array([5, 12, 1], np.int32)
    k_ref = rng.randn(S, H, max_ctx, D).astype(np.float32)
    v_ref = rng.randn(S, H, max_ctx, D).astype(np.float32)
    # scatter each slot's context token-by-token into a shared pool
    # through shuffled per-slot block tables (block 0 reserved null)
    pool_k = jnp.zeros((1 + S * nb_per, bs, H, D), jnp.float32)
    pool_v = jnp.zeros_like(pool_k)
    ids = rng.permutation(np.arange(1, 1 + S * nb_per))
    tables = ids.reshape(S, nb_per).astype(np.int32)
    for s in range(S):
        for p in range(int(ctx[s])):
            row = jnp.asarray(tables[s:s + 1])
            pos = jnp.asarray([p], jnp.int32)
            pool_k = scatter_paged_kv(pool_k, row, pos,
                                      jnp.asarray(k_ref[s:s + 1, :, p]))
            pool_v = scatter_paged_kv(pool_v, row, pos,
                                      jnp.asarray(v_ref[s:s + 1, :, p]))
    gk = np.asarray(gather_paged_kv(pool_k, jnp.asarray(tables)))
    for s in range(S):
        np.testing.assert_array_equal(gk[s, :, :ctx[s]], k_ref[s, :, :ctx[s]])
    q = jnp.asarray(rng.randn(S, H, D).astype(np.float32))
    got = paged_attention(q, pool_k, pool_v, jnp.asarray(tables),
                          jnp.asarray(ctx))
    valid = np.arange(max_ctx)[None, :] < ctx[:, None]
    mask = jnp.asarray(np.where(valid, 0.0, -1e9)[:, None, None, :],
                       jnp.float32)
    want = xla_attention(q[:, :, None, :], jnp.asarray(k_ref * valid[:, None, :, None]),
                         jnp.asarray(v_ref * valid[:, None, :, None]),
                         mask=mask)[:, :, 0, :]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


# -- engine exactness (the gate) ---------------------------------------------

def _reference(model, params, prompt, max_new, eos):
    """Per-request generate_causal greedy, trimmed EOS-inclusive."""
    import jax.numpy as jnp

    from huggingface_sagemaker_tensorflow_distributed_tpu.models.generate import (
        generate_causal,
    )

    ref = list(np.asarray(generate_causal(
        model, params, jnp.asarray(prompt)[None], max_new_tokens=max_new))[0])
    if eos in ref:
        ref = ref[:ref.index(eos) + 1]
    return [int(t) for t in ref]


def _assert_engine_exact(model, params, trace, eos, ref_model=None,
                         **engine_kw):
    """``ref_model`` overrides the generate_causal oracle — an int8
    engine's contract is generate_causal on the int8-cache config (int8
    vs fp tokens legitimately differ; quantization is deterministic)."""
    from huggingface_sagemaker_tensorflow_distributed_tpu.serve.engine import (
        ServeEngine,
    )

    ref_model = ref_model if ref_model is not None else model
    eng = ServeEngine(model, params, **engine_kw)
    reqs = [eng.submit(p, m) for p, m in trace]
    eng.run()
    for (prompt, max_new), req in zip(trace, reqs):
        got = [int(t) for t in eng.output_ids(req)]
        assert got == _reference(ref_model, params, prompt, max_new,
                                 eos), \
            f"request {req.rid} diverged (preemptions={req.preemptions})"
    return eng


def test_engine_matches_generate_causal_mixed_lengths(gpt2_setup):
    cfg, model, params = gpt2_setup
    rng = np.random.RandomState(0)
    # few DISTINCT prompt lengths: every length is a fresh XLA program
    # on the reference side, and the gate is semantics, not compile time
    trace = [(rng.randint(1, 120, (p,)).astype(np.int32), m)
             for p, m in [(5, 7), (9, 3), (12, 10), (5, 1), (9, 8)]]
    eng = _assert_engine_exact(model, params, trace, cfg.eos_token_id,
                               num_slots=3, block_size=4, num_blocks=40,
                               prefill_chunk=8, max_model_len=64)
    assert eng.stats().preemptions == 0
    assert eng.stats().tokens_generated == sum(
        len(eng.output_ids(r)) for r in eng.finished.values())


def test_engine_exact_under_preemption(gpt2_setup):
    cfg, model, params = gpt2_setup
    rng = np.random.RandomState(1)
    trace = [(rng.randint(1, 120, (9,)).astype(np.int32), 18)
             for _ in range(5)]
    # 9 allocatable blocks of 4 = 36 resident tokens for 5 requests
    # that each want 27: preemption is forced
    eng = _assert_engine_exact(model, params, trace, cfg.eos_token_id,
                               num_slots=4, block_size=4, num_blocks=10,
                               prefill_chunk=8, max_model_len=32)
    assert eng.stats().preemptions > 0


def test_engine_stops_at_eos_exactly(gpt2_setup):
    import dataclasses

    cfg, model, params = gpt2_setup
    rng = np.random.RandomState(2)
    prompt = rng.randint(1, 120, (9,)).astype(np.int32)
    # pick the reference's 3rd greedy token as EOS so the engine must
    # stop early, then rebuild the model around that id
    ref = _reference(model, params, prompt, 12, eos=-1)
    eos_cfg = dataclasses.replace(cfg, eos_token_id=int(ref[2]))
    eos_model = type(model)(eos_cfg)
    _assert_engine_exact(eos_model, params, [(prompt, 12)],
                         eos_cfg.eos_token_id, num_slots=2, block_size=4,
                         num_blocks=20, prefill_chunk=8, max_model_len=64)


def test_engine_exact_llama_gqa():
    import jax.numpy as jnp

    from huggingface_sagemaker_tensorflow_distributed_tpu.models.auto import (
        init_params,
    )
    from huggingface_sagemaker_tensorflow_distributed_tpu.models.llama import (
        LlamaConfig,
        LlamaForCausalLM,
    )

    cfg = LlamaConfig(vocab_size=128, hidden_size=32, num_layers=2,
                      num_heads=4, num_kv_heads=2, intermediate_size=64,
                      max_position_embeddings=128, eos_token_id=127,
                      pad_token_id=0, dtype=jnp.float32)
    model = LlamaForCausalLM(cfg)
    params = init_params(model, cfg, seed=0)
    rng = np.random.RandomState(3)
    trace = [(rng.randint(3, 120, (p,)).astype(np.int32), m)
             for p, m in [(6, 6), (11, 9), (6, 4)]]
    _assert_engine_exact(model, params, trace, cfg.eos_token_id,
                         num_slots=2, block_size=8, num_blocks=20,
                         prefill_chunk=8, max_model_len=64)


def test_engine_rejects_unsupported_configs(gpt2_setup):
    """The ISSUE 3 rejection surface after ISSUE 9: int8-KV and
    sliding-window configs are now SERVED (their engines construct and
    carry the right pool dtypes), and the rejections that remain are
    genuine unsupported shapes plus unparseable knob values."""
    import dataclasses

    from huggingface_sagemaker_tensorflow_distributed_tpu.serve.engine import (
        ServeEngine,
    )

    cfg, model, params = gpt2_setup
    int8 = type(model)(dataclasses.replace(cfg, kv_cache_dtype="int8"))
    eng = ServeEngine(int8, params, num_blocks=4, block_size=4,
                      max_model_len=16, prefill_chunk=8)
    assert eng.kv_cache_dtype == "int8"
    assert {str(p.dtype) for p in eng._pools} == {"int8", "float32"}
    # the knob form: an fp model rebuilt around int8 pool storage
    eng = ServeEngine(model, params, num_blocks=4, block_size=4,
                      max_model_len=16, prefill_chunk=8,
                      kv_cache_dtype="int8")
    assert eng.model.config.kv_cache_dtype == "int8"
    with pytest.raises(ValueError, match="max_position_embeddings"):
        ServeEngine(model, params, num_blocks=4, max_model_len=1024)
    with pytest.raises(ValueError, match="HSTD_SERVE_KERNEL"):
        ServeEngine(model, params, num_blocks=4, block_size=4,
                    max_model_len=16, prefill_chunk=8, kernel="cuda")
    with pytest.raises(ValueError, match="HSTD_SERVE_KV_DTYPE"):
        ServeEngine(model, params, num_blocks=4, block_size=4,
                    max_model_len=16, prefill_chunk=8,
                    kv_cache_dtype="fp8")


# -- telemetry ---------------------------------------------------------------

def test_engine_emits_valid_serve_events(gpt2_setup, tmp_path):
    cfg, model, params = gpt2_setup
    out = tmp_path / "telemetry"
    obs.reset(out_dir=str(out), enabled=True)
    try:
        rng = np.random.RandomState(4)
        trace = [(rng.randint(1, 120, (5,)).astype(np.int32), 7)
                 for _ in range(3)]
        _assert_engine_exact(model, params, trace, cfg.eos_token_id,
                             num_slots=2, block_size=4, num_blocks=20,
                             prefill_chunk=8, max_model_len=64)
        obs.flush()
    finally:
        obs.reset()
    events = [e for _, e, err in obs.iter_events(str(out / "events.jsonl"))
              if err is None]
    serve_ev = [e for e in events if e["type"] == "serve"]
    kinds = {e["event"] for e in serve_ev}
    assert {"submit", "admit", "first_token", "finish"} <= kinds
    finishes = [e for e in serve_ev if e["event"] == "finish"]
    assert len(finishes) == 3 and all("request" in e for e in finishes)
    ttfts = [e for e in serve_ev if e["event"] == "first_token"]
    assert all(e.get("ttft_s", 0) > 0 for e in ttfts)
    count, errors = obs.validate_events_file(str(out / "events.jsonl"))
    assert not errors and count >= len(events)


def test_generate_causal_decode_phase_split_telemetry(gpt2_setup, tmp_path):
    """ROADMAP "Decode-phase split": the one-shot path now reports TTFT
    and decode tokens/sec as separate series, with prefill and decode
    visible as separate spans."""
    import jax.numpy as jnp

    from huggingface_sagemaker_tensorflow_distributed_tpu.models.generate import (
        generate_causal,
    )

    cfg, model, params = gpt2_setup
    out = tmp_path / "telemetry"
    obs.reset(out_dir=str(out), enabled=True)
    try:
        prompt = np.random.RandomState(5).randint(1, 120, (1, 6))
        generate_causal(model, params, jnp.asarray(prompt),
                        max_new_tokens=4)
        obs.flush()
    finally:
        obs.reset()
    events = [e for _, e, err in obs.iter_events(str(out / "events.jsonl"))
              if err is None]
    metrics = {e["name"] for e in events if e["type"] == "metric"}
    assert "generate/causal_ttft_s" in metrics
    assert "generate/causal_decode_tokens_per_sec" in metrics
    spans = {e["name"] for e in events if e["type"] == "span"}
    assert {"generate/causal_prefill", "generate/causal_decode"} <= spans

# -- ISSUE 5 decode fast path: bucketed gather, batched prefill, sampling ----

def test_parse_gather_buckets_ladder():
    from huggingface_sagemaker_tensorflow_distributed_tpu.serve.engine import (
        parse_gather_buckets,
    )

    # auto: quarter width + full width, block-rounded
    assert parse_gather_buckets(None, 512, 16) == [128, 512]
    assert parse_gather_buckets("auto", 64, 8) == [16, 64]
    # explicit env form: rounded UP to block multiples, clipped, full
    # width always present, dedup + sorted
    assert parse_gather_buckets("60,200,9999", 512, 16) == [64, 208, 512]
    # "full" disables bucketing
    assert parse_gather_buckets("full", 512, 16) == [512]
    # sequences work too (engine kwarg form)
    assert parse_gather_buckets([64, 512], 512, 16) == [64, 512]
    with pytest.raises(ValueError, match="unparseable"):
        parse_gather_buckets("wide", 512, 16)


def test_gather_bucket_width_matches_full_width_at_boundaries():
    """ops-level bucket contract: for contexts at bucket-1 / bucket /
    bucket+1, the width-restricted gather returns exactly the first
    `width` logical positions of the full-width gather."""
    import jax.numpy as jnp

    from huggingface_sagemaker_tensorflow_distributed_tpu.ops.attention import (
        gather_paged_kv,
    )

    rng = np.random.RandomState(7)
    bs, nb_per, S, H, D = 4, 6, 2, 2, 3          # span 24, bucket 8
    pool = jnp.asarray(rng.randn(1 + S * nb_per, bs, H, D)
                       .astype(np.float32))
    tables = jnp.asarray(
        rng.permutation(np.arange(1, 1 + S * nb_per))
        .reshape(S, nb_per).astype(np.int32))
    full = np.asarray(gather_paged_kv(pool, tables))
    for width in (8, 16):
        got = np.asarray(gather_paged_kv(pool, tables, width=width))
        np.testing.assert_array_equal(got, full[:, :, :width])
    with pytest.raises(ValueError, match="multiple"):
        gather_paged_kv(pool, tables, width=10)
    with pytest.raises(ValueError, match="block table holds"):
        gather_paged_kv(pool, tables, width=32)


def test_engine_exact_across_bucket_boundaries(gpt2_setup):
    """The tentpole exactness gate at every bucket boundary: resident
    contexts hit bucket-1, bucket, and bucket+1 (prompt lengths 15/16/17
    against a 16-wide first bucket, decode crossing it mid-request), and
    the greedy stream must stay token-for-token generate_causal."""
    cfg, model, params = gpt2_setup
    rng = np.random.RandomState(6)
    trace = [(rng.randint(1, 120, (p,)).astype(np.int32), 6)
             for p in (15, 16, 17)]
    eng = _assert_engine_exact(model, params, trace, cfg.eos_token_id,
                               num_slots=3, block_size=4, num_blocks=40,
                               prefill_chunk=8, max_model_len=64,
                               gather_buckets=[16, 32])
    assert eng.gather_buckets == [16, 32, 64]
    # decode really ran below full width (the fast path engaged) and
    # crossing the boundary forced at least one bucket switch
    assert eng.bucket_switches >= 1
    assert eng.stats().gather_waste_mean < 1.0


def test_batched_prefill_isolation_and_batching(gpt2_setup):
    """Batched prefill packs concurrent prompts into one dispatch
    (fewer dispatches than chunks) without cross-request leakage: every
    request's stream equals its solo generate_causal reference, and a
    request served alongside others equals the same request served
    ALONE."""
    cfg, model, params = gpt2_setup
    rng = np.random.RandomState(8)
    prompts = [rng.randint(1, 120, (11,)).astype(np.int32)
               for _ in range(4)]
    trace = [(p, 5) for p in prompts]
    eng = _assert_engine_exact(model, params, trace, cfg.eos_token_id,
                               num_slots=4, block_size=4, num_blocks=60,
                               prefill_chunk=8, max_model_len=64)
    # 4 requests x 2 chunks each admitted together: batching must pack
    # them (strictly fewer dispatches than chunks)
    assert eng.prefill_dispatches < eng.prefill_chunks
    from huggingface_sagemaker_tensorflow_distributed_tpu.serve.engine import (
        ServeEngine,
    )

    solo = ServeEngine(model, params, num_slots=4, block_size=4,
                       num_blocks=60, prefill_chunk=8, max_model_len=64)
    req = solo.submit(prompts[0], 5)
    solo.run()
    batched_req = next(r for r in eng.finished.values()
                       if list(r.prompt[:11]) == list(prompts[0]))
    assert list(solo.output_ids(req)) == list(eng.output_ids(batched_req))


def test_sampled_serve_is_seed_deterministic_across_preemption(gpt2_setup):
    """The seeded-determinism gate for sampled mode: identical seeds
    reproduce bitwise-identical streams, preemption/requeue does not
    change them, a different seed changes only its own stream, and
    greedy requests in the same batch stay exactly generate_causal."""
    cfg, model, params = gpt2_setup
    rng = np.random.RandomState(9)
    trace = [(rng.randint(1, 120, (9,)).astype(np.int32), 14)
             for _ in range(4)]
    kws = [dict(temperature=0.9, top_k=20, top_p=0.9, seed=s)
           for s in (1, 2, 3)] + [dict()]        # request 3 stays greedy

    def run(num_blocks, kws):
        from huggingface_sagemaker_tensorflow_distributed_tpu.serve.engine import (
            ServeEngine,
        )

        eng = ServeEngine(model, params, num_slots=3, block_size=4,
                          num_blocks=num_blocks, prefill_chunk=8,
                          max_model_len=32)
        reqs = [eng.submit(p, m, **kw) for (p, m), kw in zip(trace, kws)]
        eng.run()
        return [[int(t) for t in eng.output_ids(r)] for r in reqs], eng

    base, eng = run(40, kws)
    again, _ = run(40, kws)
    assert again == base                        # bitwise reproducible
    tight, teng = run(9, kws)                   # tight pool: preemption
    assert teng.stats().preemptions > 0
    assert tight == base                        # preemption-invariant
    reseeded, _ = run(40, [dict(kws[0], seed=99)] + kws[1:])
    assert reseeded[0] != base[0]               # the seed matters
    assert reseeded[1:] == base[1:]             # ...only for its stream
    # the greedy rider is untouched by its sampled batchmates
    p, m = trace[3]
    assert base[3] == _reference(model, params, p, m, cfg.eos_token_id)


def test_request_rejects_bad_sampling_params():
    with pytest.raises(ValueError, match="temperature"):
        Request(prompt=np.arange(1, 4), max_new_tokens=2, temperature=-1.0)
    with pytest.raises(ValueError, match="top_p"):
        Request(prompt=np.arange(1, 4), max_new_tokens=2, top_p=1.5)
    with pytest.raises(ValueError, match="top_k"):
        Request(prompt=np.arange(1, 4), max_new_tokens=2, top_k=-2)


# -- ISSUE 6: speculative decoding inside the engine -------------------------

@pytest.fixture(scope="module")
def spec_draft():
    """An INDEPENDENTLY-initialized 1-layer draft over the gpt2_setup
    vocabulary: disagrees with the target often enough that rejection /
    rewind paths are genuinely exercised (a self-draft of a tiny
    random-init model is near-perfect — upper blocks are ~identity)."""
    import jax.numpy as jnp

    from huggingface_sagemaker_tensorflow_distributed_tpu.models.auto import (
        init_params,
    )
    from huggingface_sagemaker_tensorflow_distributed_tpu.models.gpt2 import (
        Gpt2Config,
        Gpt2LMHeadModel,
    )

    cfg = Gpt2Config(vocab_size=128, hidden_size=32, num_layers=1,
                     num_heads=2, intermediate_size=64,
                     max_position_embeddings=128, hidden_dropout=0.0,
                     embd_dropout=0.0, attention_dropout=0.0,
                     eos_token_id=127, pad_token_id=0, dtype=jnp.float32)
    model = Gpt2LMHeadModel(cfg)
    return model, init_params(model, cfg, seed=5)


def test_speculative_engine_exact_across_bucket_boundaries(gpt2_setup,
                                                           spec_draft):
    """The tentpole exactness gate, speculative edition: greedy
    draft-k/verify serving stays token-for-token generate_causal with
    resident contexts crossing every bucket boundary (prompts 15/16/17
    against a 16-wide first bucket) and an adversarial draft forcing
    real rejections (acceptance < 1) — the context-rewind path is load-
    bearing, not idle."""
    cfg, model, params = gpt2_setup
    rng = np.random.RandomState(6)
    trace = [(rng.randint(1, 120, (p,)).astype(np.int32), 6)
             for p in (15, 16, 17)]
    eng = _assert_engine_exact(model, params, trace, cfg.eos_token_id,
                               num_slots=3, block_size=4, num_blocks=40,
                               prefill_chunk=8, max_model_len=64,
                               gather_buckets=[16, 32],
                               speculate_k=2, draft=spec_draft)
    assert eng.gather_buckets == [16, 32, 64]
    stats = eng.stats()
    assert stats.draft_proposed > 0
    assert 0 <= stats.acceptance_rate < 1     # rejections actually hit
    assert stats.spec_windows > 0
    assert 0 < stats.verify_waste_mean < 1    # rejected tails accounted
    # no block leaked through the window-reserve/commit/trim cycle
    # (prefix caching keeps finished prompts' blocks CACHED, not free —
    # conservation counts both)
    assert (eng.blocks.num_free + eng.blocks.num_cached
            == eng.blocks.num_blocks - 1)
    assert eng.blocks.num_used == 0


def test_speculative_engine_exact_under_preemption_rewind_leak_free(
        gpt2_setup, spec_draft):
    """Forced recompute preemption + rejection storms: outputs stay
    exact, and every block comes back to the free list (no lost /
    double-freed blocks across grow-for-window -> reject -> trim ->
    preempt cycles)."""
    cfg, model, params = gpt2_setup
    rng = np.random.RandomState(1)
    trace = [(rng.randint(1, 120, (9,)).astype(np.int32), 14)
             for _ in range(5)]
    eng = _assert_engine_exact(model, params, trace, cfg.eos_token_id,
                               num_slots=4, block_size=4, num_blocks=11,
                               prefill_chunk=8, max_model_len=32,
                               speculate_k=2, draft=spec_draft)
    assert eng.stats().preemptions > 0
    assert (eng.blocks.num_free + eng.blocks.num_cached
            == eng.blocks.num_blocks - 1)
    assert eng.blocks.num_used == 0


def test_sampled_speculative_serve_seed_deterministic_across_preemption(
        gpt2_setup, spec_draft):
    """Extends the ISSUE 5 seeded-determinism gate to speculative mode:
    the whole verify window's randomness derives from (request seed,
    window-start token index), so sampled speculative streams are
    bitwise seed-reproducible INCLUDING across recompute preemption
    (windows re-start at the same committed index), reseeding changes
    only its own stream, and a greedy rider stays generate_causal."""
    cfg, model, params = gpt2_setup
    rng = np.random.RandomState(9)
    trace = [(rng.randint(1, 120, (9,)).astype(np.int32), 14)
             for _ in range(4)]
    kws = [dict(temperature=0.9, top_k=20, top_p=0.9, seed=s)
           for s in (1, 2, 3)] + [dict()]        # request 3 stays greedy

    def run(num_blocks, kws):
        from huggingface_sagemaker_tensorflow_distributed_tpu.serve.engine import (
            ServeEngine,
        )

        eng = ServeEngine(model, params, num_slots=3, block_size=4,
                          num_blocks=num_blocks, prefill_chunk=8,
                          max_model_len=32, speculate_k=2,
                          draft=spec_draft)
        reqs = [eng.submit(p, m, **kw) for (p, m), kw in zip(trace, kws)]
        eng.run()
        return [[int(t) for t in eng.output_ids(r)] for r in reqs], eng

    base, eng = run(40, kws)
    assert eng.stats().draft_proposed > 0
    again, _ = run(40, kws)
    assert again == base                        # bitwise reproducible
    tight, teng = run(11, kws)                  # tight pool: preemption
    assert teng.stats().preemptions > 0
    assert tight == base                        # preemption-invariant
    reseeded, _ = run(40, [dict(kws[0], seed=99)] + kws[1:])
    assert reseeded[0] != base[0]               # the seed matters
    assert reseeded[1:] == base[1:]             # ...only for its stream
    p, m = trace[3]
    assert base[3] == _reference(model, params, p, m, cfg.eos_token_id)


def test_speculative_engine_knobs_and_rejections(gpt2_setup, spec_draft,
                                                 monkeypatch):
    """Constructor/env contract: env-driven speculate_k, ladder pruning
    of sub-window buckets, window-aware submit rejection, bad-knob
    errors. Host-side only — nothing here dispatches."""
    from huggingface_sagemaker_tensorflow_distributed_tpu.serve.engine import (
        ENV_SPECULATE_K,
        ServeEngine,
    )

    cfg, model, params = gpt2_setup
    kw = dict(num_slots=2, block_size=4, num_blocks=20, prefill_chunk=8,
              max_model_len=32)
    monkeypatch.setenv(ENV_SPECULATE_K, "2")
    eng = ServeEngine(model, params, draft=spec_draft, **kw)
    assert eng.speculate_k == 2 and eng.speculative
    monkeypatch.delenv(ENV_SPECULATE_K)
    # the engine-level window reservation: prompt + max_new + k must
    # fit max_model_len (the verify window writes k past the last
    # committed position)
    with pytest.raises(ValueError, match="verify-window"):
        eng.submit(np.arange(1, 9), 24)       # 8 + 24 + 2 > 32
    eng.submit(np.arange(1, 9), 22)           # 8 + 22 + 2 == 32: fits
    # buckets narrower than the window can never be selected: pruned
    sp = ServeEngine(model, params, speculate_k=7, draft=spec_draft,
                     gather_buckets=[4, 16], **kw)
    assert sp.gather_buckets == [16, 32]
    with pytest.raises(ValueError, match="speculate_k"):
        ServeEngine(model, params, speculate_k=-1, **kw)
    with pytest.raises(ValueError, match="vocabulary"):
        import dataclasses

        other_cfg = dataclasses.replace(spec_draft[0].config,
                                        vocab_size=64)
        other = type(spec_draft[0])(other_cfg)
        ServeEngine(model, params, speculate_k=2,
                    draft=(other, spec_draft[1]), **kw)


def test_warmup_sampled_precompiles_sampled_variants(gpt2_setup, tmp_path):
    """The ROADMAP `warmup(sampled=True)` knob: after it, sampled
    traffic triggers ZERO mid-serve compiles (without it the sampled
    step variants compile lazily on the first sampled batch)."""
    from huggingface_sagemaker_tensorflow_distributed_tpu.serve.engine import (
        ServeEngine,
    )

    cfg, model, params = gpt2_setup
    obs.reset(out_dir=str(tmp_path / "telemetry"), enabled=True)
    try:
        eng = ServeEngine(model, params, num_slots=3, block_size=4,
                          num_blocks=40, prefill_chunk=8,
                          max_model_len=64)
        eng.warmup(sampled=True)
        tracker = obs.compile_tracker()
        count0 = tracker.count
        rng = np.random.RandomState(12)
        for s in range(3):
            eng.submit(rng.randint(1, 120, (9,)).astype(np.int32), 8,
                       temperature=0.8, top_k=10, seed=s)
        eng.run()
        assert tracker.count == count0, \
            "sampled serving recompiled after warmup(sampled=True)"
    finally:
        obs.reset()


def test_block_manager_gather_waste_accounting():
    """note_gather latches the PEAK bucket-padded read waste and keeps
    a token-weighted mean — the decode-side counterpart of allocation
    fragmentation."""
    bm = BlockManager(num_blocks=9, block_size=4)
    assert bm.gather_waste() == 0.0 and bm.peak_gather_waste == 0.0
    # 2 slots read at width 16 holding 4+8 useful -> waste 1 - 12/32
    assert bm.note_gather([4, 8], 16) == pytest.approx(1 - 12 / 32)
    # a tighter step: 2 slots at width 8 holding 7+8 -> 1 - 15/16
    assert bm.note_gather([7, 8], 8) == pytest.approx(1 - 15 / 16)
    assert bm.peak_gather_waste == pytest.approx(1 - 12 / 32)
    assert bm.gather_waste() == pytest.approx(1 - 27 / 48)
    assert bm.note_gather([], 16) == 0.0        # empty step: no-op


def test_block_manager_verify_waste_is_separate_from_gather_waste():
    """note_verify accounts width-(k+1) window padding (rejected draft
    tails) in ITS OWN accumulators — a speculative engine can have high
    verify waste with low bucket-read waste and vice versa, and the
    report must tell them apart."""
    bm = BlockManager(num_blocks=9, block_size=4)
    assert bm.verify_waste() == 0.0 and bm.peak_verify_waste == 0.0
    # 2 windows of width 5 committing 5 and 2 tokens -> 1 - 7/10
    assert bm.note_verify([5, 2], 5) == pytest.approx(1 - 7 / 10)
    # a fully-accepted step: zero waste, peak latched from before
    assert bm.note_verify([5, 5], 5) == 0.0
    assert bm.peak_verify_waste == pytest.approx(1 - 7 / 10)
    assert bm.verify_waste() == pytest.approx(1 - 17 / 20)
    assert bm.note_verify([], 5) == 0.0         # empty step: no-op
    # gather-side accumulators untouched
    assert bm.gather_waste() == 0.0 and bm.peak_gather_waste == 0.0


# -- ISSUE 8: copy-on-write prefix caching -----------------------------------

def test_block_manager_double_free_guard():
    """The satellite hard-guard: release()/free()/trim() on a block id
    that is no longer held raises instead of silently corrupting the
    free list (fatal once refcounts share blocks across requests)."""
    bm = BlockManager(num_blocks=9, block_size=4)
    got = bm.allocate(2)
    bm.release(got)
    with pytest.raises(ValueError, match="double free"):
        bm.release([got[0]])                     # already on the free list
    with pytest.raises(ValueError, match="double free"):
        bm.free([got[1]])                        # legacy alias, same guard
    # trim routes through release: a table holding an already-released
    # id must raise, not push the id onto the free list twice
    stale = [bm.allocate(1)[0], got[0]]
    with pytest.raises(ValueError, match="double free"):
        bm.trim(stale, 0)
    # a zero-ref CACHED block is not held either: releasing it again
    # must raise, not corrupt the LRU/free accounting
    t = bm.allocate(1)
    bm.register_prefix(np.arange(1, 5), t)
    bm.release(t)
    assert bm.num_cached == 1
    with pytest.raises(ValueError, match="double free"):
        bm.release(t)


def test_block_manager_prefix_match_register_lru_roundtrip():
    """The prefix-index lifecycle: register publishes full prompt
    blocks, match increfs them (chain-verified — a diverging prompt
    misses from the divergence block on), release parks zero-ref
    registered blocks in the LRU (reusable, counted as capacity), and
    allocation pressure evicts oldest-first, after which the lookup
    misses."""
    bm = BlockManager(num_blocks=8, block_size=4)     # 7 allocatable
    prompt = np.arange(1, 14)                         # 13 tokens, 3 full blocks
    table = bm.allocate(4)                            # ceil(13/4)
    bm.register_prefix(prompt, table)
    # another request with the same prompt start shares all 3 full blocks
    hit = bm.match_prefix(prompt)
    assert hit == table[:3]
    assert bm.blocks_saved() == 3                     # 3 dedup'd blocks
    # a prompt diverging INSIDE block 1 matches only block 0
    other = np.concatenate([prompt[:6], [99, 98, 97, 96]])
    hit2 = bm.match_prefix(other)
    assert hit2 == table[:1]
    bm.release(hit2)
    # a cap: the caller can bound the walk (engine leaves the final
    # prompt token uncached)
    assert bm.match_prefix(prompt, max_blocks=2) == table[:2]
    bm.release(table[:2])
    bm.release(hit)
    bm.release(table)                                 # original owner done
    assert bm.num_used == 0 and bm.num_cached == 3
    assert bm.can_allocate(7)                         # cached = capacity
    # pressure: allocating past the free list evicts oldest (block 0's
    # chunk) — the chain then misses at level 0, so NOTHING matches
    got = bm.allocate(5)
    assert bm.num_cached == 2 and bm.prefix_evictions == 1
    assert bm.match_prefix(prompt) == []
    bm.release(got)


def test_block_manager_privatize_cow_semantics():
    """privatize(): refcount > 1 => fresh private copy (src/dst device
    copy returned, source stays with the other holder); sole-owner
    registered => unpublish + write in place (no copy)."""
    bm = BlockManager(num_blocks=9, block_size=4)
    prompt = np.arange(1, 9)                          # 2 full blocks
    table = bm.allocate(2)
    bm.register_prefix(prompt, table)
    sharer = bm.match_prefix(prompt)                  # refs now 2/2
    copies = bm.privatize(sharer, 0, 1)
    assert len(copies) == 1 and copies[0][0] == table[0]
    assert sharer[0] != table[0] and bm.cow_copies == 1
    assert bm.is_private(sharer[0])
    # the source block is still the registered original at ref 1
    assert bm.match_prefix(prompt, max_blocks=1) == [table[0]]
    bm.release([table[0]])
    # sole-owner registered block: in-place unpublish, no copy
    bm.release(sharer)                                # drop the sharer refs
    bm.release([table[1]])                            # table now fully cached
    mine = bm.match_prefix(prompt)                    # revive both at ref 1
    assert bm.privatize(mine, 1, 2) == []
    assert bm.is_private(mine[1])                     # unregistered now
    assert bm.match_prefix(prompt, max_blocks=2) == [table[0]]
    bm.release([table[0]])
    bm.release(mine)
    bm.release([table[0]])                            # the allocate() ref
    assert bm.num_used == 0


def test_block_conservation_under_random_schedule(rng):
    """The satellite property test: across a randomized
    submit/admit/prefill/decode/preempt/finish/share/COW schedule with
    prefix caching on (small pool => LRU eviction pressure) PLUS the
    ISSUE 17 host tier (a stand-in spill/swap hook drives swap-out /
    swap-in / demote / revive / payload-evict through the same
    churn), every step preserves ``num_free + num_used + num_cached +
    num_hosted == num_blocks - 1``, every table reference is backed by
    exactly its refcount, no table references a freed block, and
    hosted blocks are never simultaneously free or held."""
    from collections import Counter
    from types import SimpleNamespace

    bm = BlockManager(num_blocks=20, block_size=4)
    # chunk 8 vs block 4: a cached prefix of 12 tokens re-aligns to
    # chunk 8, so admissions privatize (COW) the overlap block when the
    # original holder is still resident
    s = Scheduler(3, bm, 8, 32, prefix_cache=True)
    prefixes = [rng.randint(1, 100, (12,)).astype(np.int32),
                rng.randint(1, 100, (20,)).astype(np.int32)]
    # the host tier, engine-free: payloads are opaque (conservation is
    # about IDs, not bytes) and the budget is tight enough that
    # reserve failures and oldest-first payload eviction both happen
    bm.set_spill(lambda b: SimpleNamespace(nbytes=64), host_budget=1024)

    def swap_hook(slot):
        if not rng.randint(0, 2):
            return False                     # the recompute arm
        req = slot.request
        n = bm.blocks_for(slot.context_len)
        if n <= 0 or n > len(slot.table):
            return False
        if not bm.host_reserve(n * 64):
            return False                     # budget starved: recompute
        req.swap_set = SimpleNamespace(n_blocks=n, nbytes=n * 64)
        req.swap_context = slot.context_len
        return True

    s.swap_hook = swap_hook

    def check():
        assert (bm.num_free + bm.num_used + bm.num_cached
                + bm.num_hosted == bm.num_blocks - 1)
        held = Counter(b for slot in s.slots if not slot.free
                       for b in slot.table)
        refs = {b: bm._ref[b] for b in range(1, bm.num_blocks)
                if bm._ref[b] > 0}
        assert dict(held) == refs            # every ref is a table ref
        free_set = set(bm._free)
        assert not (set(held) & free_set)    # no table refs a freed block
        assert 0 not in held                 # the null block is never owned
        hosted = set(bm._hosted)
        assert not (hosted & free_set)       # demoted ids are resident
        assert not (hosted & set(held))      # ...and zero-ref

    for step in range(300):
        op = rng.randint(0, 6)
        if op == 5 and bm.host_tier_active:  # demotion pressure
            bm.demote(max_blocks=int(rng.randint(1, 3)))
        elif op == 0 and len(s.waiting) < 4:
            if rng.randint(0, 2):
                pre = prefixes[rng.randint(0, len(prefixes))]
                tail = rng.randint(1, 100,
                                   (rng.randint(1, 6),)).astype(np.int32)
                prompt = np.concatenate([pre, tail])
            else:
                prompt = rng.randint(
                    1, 100, (rng.randint(1, 16),)).astype(np.int32)
            try:
                s.submit(Request(prompt=prompt,
                                 max_new_tokens=int(rng.randint(1, 5))))
            except ValueError:
                pass                          # over-length: rejected
        elif op == 1:
            s.admit()
        elif op == 2:                         # one prefill chunk everywhere
            for slot in s.next_prefill_slots(3):
                slot.prefill_pos += s.prefill_chunk
                if slot.prefill_pos >= s.padded_prompt_len(slot.request):
                    s.finish_prefill(slot)
        elif op == 3:                         # one decode step
            try:
                s.ensure_decode_capacity()
            except PoolExhausted:
                pass
            for slot in s.decode_slots():
                req = slot.request
                slot.context_len += 1
                req.output.append(0)
                if len(req.output) >= req.max_new_tokens:
                    s.finish(slot)
        elif op == 4:                         # forced preemption
            ds = s.decode_slots()
            if ds:
                s.preempt(ds[int(rng.randint(0, len(ds)))])
        check()
    # drain: preempted/waiting requests release nothing further; every
    # running request's blocks come back on finish
    for slot in s.slots:
        if not slot.free:
            s.finish(slot)
    check()
    assert bm.num_used == 0


def _prefix_trace(rng, prefix_len, tails, max_news, vocab=120):
    """Requests sharing one random prefix with varied random tails."""
    prefix = rng.randint(1, vocab, (prefix_len,)).astype(np.int32)
    return [(np.concatenate([prefix,
                             rng.randint(1, vocab, (t,)).astype(np.int32)])
             if t else prefix.copy(), m)
            for t, m in zip(tails, max_news)]


def test_prefix_cache_serve_token_exact_with_forced_cow(gpt2_setup):
    """The tentpole exactness gate: shared-prefix serving is
    token-identical to cold start (greedy vs generate_causal), with
    real sharing (later requests' prefill skips cached chunks) AND
    forced copy-on-write — block_size 4 under chunk 8 re-aligns a
    12-token cached prefix to chunk 8, so a request diverging from a
    still-resident sharer mid-chunk must privatize the overlap block
    before scattering into it."""
    cfg, model, params = gpt2_setup
    rng = np.random.RandomState(21)
    # A long-running (max_new 14), then short riders sharing its
    # 12-token prefix admitted AFTER A registered — while A still
    # holds its blocks, so the overlap block's refcount is > 1
    trace = _prefix_trace(rng, 12, tails=[3, 0, 2, 1, 2],
                          max_news=[14, 2, 4, 3, 4])
    eng = _assert_engine_exact(model, params, trace, cfg.eos_token_id,
                               num_slots=2, block_size=4, num_blocks=40,
                               prefill_chunk=8, max_model_len=32)
    assert eng.prefix_cache
    reqs = list(eng.finished.values())
    assert sum(r.prefix_cached_tokens for r in reqs) > 0   # real hits
    assert eng.blocks.cow_copies > 0                       # real COW
    assert eng.stats().cache_hit_rate > 0
    assert eng.stats().blocks_shared_peak > 0
    # conservation after the run: everything free or cached, none held
    assert eng.blocks.num_used == 0
    assert (eng.blocks.num_free + eng.blocks.num_cached
            == eng.blocks.num_blocks - 1)


def test_prefix_cache_exact_under_preemption_of_sharing_request(gpt2_setup):
    """Forced recompute preemption OF a prefix-sharing request: only
    its private references release (other holders and the cache keep
    the shared blocks), the resumed request re-hits the cache for its
    folded prompt, and every stream stays token-exact."""
    cfg, model, params = gpt2_setup
    rng = np.random.RandomState(22)
    trace = _prefix_trace(rng, 12, tails=[2, 3, 1, 2, 3],
                          max_news=[12, 12, 12, 12, 12])
    # 11 allocatable blocks of 4 for five 14-15 token prompts that each
    # want 12 more: preemption is forced even WITH sharing
    eng = _assert_engine_exact(model, params, trace, cfg.eos_token_id,
                               num_slots=4, block_size=4, num_blocks=12,
                               prefill_chunk=8, max_model_len=32)
    assert eng.stats().preemptions > 0
    assert sum(r.prefix_cached_tokens
               for r in eng.finished.values()) > 0
    assert eng.blocks.num_used == 0
    assert (eng.blocks.num_free + eng.blocks.num_cached
            == eng.blocks.num_blocks - 1)


def test_prefix_cache_speculative_serve_exact(gpt2_setup, spec_draft):
    """Prefix caching composes with speculative decode: the draft's
    pools ride the same shared block tables (COW copies apply to both
    address spaces), greedy stays token-exact, and the verify-window
    trim never releases a shared block."""
    cfg, model, params = gpt2_setup
    rng = np.random.RandomState(23)
    trace = _prefix_trace(rng, 12, tails=[3, 0, 2, 1], max_news=[12, 3, 5, 4])
    eng = _assert_engine_exact(model, params, trace, cfg.eos_token_id,
                               num_slots=2, block_size=4, num_blocks=40,
                               prefill_chunk=8, max_model_len=32,
                               speculate_k=2, draft=spec_draft)
    assert sum(r.prefix_cached_tokens
               for r in eng.finished.values()) > 0
    assert eng.stats().draft_proposed > 0
    assert eng.blocks.num_used == 0
    assert (eng.blocks.num_free + eng.blocks.num_cached
            == eng.blocks.num_blocks - 1)


def test_prefix_cache_off_matches_on_and_stays_cold(gpt2_setup):
    """The regression-tax gate: prefix_cache='off' serves the exact
    same tokens as 'on' (and the cold reference), never touches the
    index/LRU/COW machinery, and a sampled trace stays bitwise
    seed-identical across on/off — the cache must be semantically
    invisible either way."""
    from huggingface_sagemaker_tensorflow_distributed_tpu.serve.engine import (
        ServeEngine,
    )

    cfg, model, params = gpt2_setup
    rng = np.random.RandomState(24)
    trace = _prefix_trace(rng, 12, tails=[3, 1, 2, 2], max_news=[8, 6, 7, 5])
    kws = [dict(), dict(temperature=0.9, top_k=20, top_p=0.9, seed=7),
           dict(), dict(temperature=0.7, seed=3)]

    def run(prefix_cache):
        eng = ServeEngine(model, params, num_slots=3, block_size=4,
                          num_blocks=40, prefill_chunk=8,
                          max_model_len=32, prefix_cache=prefix_cache)
        reqs = [eng.submit(p, m, **kw)
                for (p, m), kw in zip(trace, kws)]
        eng.run()
        return [[int(t) for t in eng.output_ids(r)] for r in reqs], eng

    on, eng_on = run("on")
    off, eng_off = run("off")
    assert on == off
    assert not eng_off.prefix_cache
    assert eng_off.blocks.num_cached == 0          # machinery inert
    assert eng_off.blocks.cow_copies == 0
    assert eng_off.blocks.peak_shared_blocks == 0
    assert all(r.prefix_cached_tokens == 0
               for r in eng_off.finished.values())
    assert eng_off.stats().cache_hit_rate is None
    # off: every block comes straight back to the free list (PR 6
    # behavior byte-for-byte)
    assert eng_off.blocks.num_free == eng_off.blocks.num_blocks - 1
    # the greedy rows also equal the cold per-request reference
    for (p, m), kw, out in zip(trace, kws, on):
        if not kw:
            assert out == _reference(model, params, p, m,
                                     cfg.eos_token_id)


def test_parse_prefix_cache_knob(monkeypatch):
    from huggingface_sagemaker_tensorflow_distributed_tpu.serve.engine import (
        ENV_PREFIX_CACHE,
        parse_prefix_cache,
    )

    assert parse_prefix_cache(None) is True        # default on
    assert parse_prefix_cache("off") is False
    assert parse_prefix_cache("on") is True
    assert parse_prefix_cache(False) is False
    monkeypatch.setenv(ENV_PREFIX_CACHE, "off")
    assert parse_prefix_cache(None) is False
    monkeypatch.setenv(ENV_PREFIX_CACHE, "banana")
    with pytest.raises(ValueError, match="unparseable"):
        parse_prefix_cache(None)


def test_scheduler_lookahead_reserves_verify_window():
    """decode_lookahead generalizes the +1 decode reservation: submit
    rejects requests whose window would overflow max_model_len, and
    ensure_decode_capacity grows tables to context + lookahead."""
    bm = BlockManager(num_blocks=20, block_size=4)
    s = Scheduler(1, bm, 4, 32, decode_lookahead=4)     # k = 3
    with pytest.raises(ValueError, match="verify-window"):
        s.submit(Request(prompt=np.arange(1, 9), max_new_tokens=22))
    s.submit(Request(prompt=np.arange(1, 9), max_new_tokens=21))
    s.admit()
    slot = s.slots[0]
    s.finish_prefill(slot)
    assert s.max_decode_context() == 8 + 4
    s.ensure_decode_capacity()
    # table covers context + lookahead = 12 tokens -> 3 blocks
    assert len(slot.table) == 3


# -- ISSUE 9: fused paged-attention kernel + int8 KV pools -------------------

def _int8_model(model, cfg):
    import dataclasses

    return type(model)(dataclasses.replace(cfg, kv_cache_dtype="int8"))


def test_engine_exact_with_pallas_kernel(gpt2_setup):
    """The ISSUE 9 tentpole gate: with the fused Pallas decode kernel
    engaged (interpret mode on CPU), the engine stays token-for-token
    generate_causal — across bucket boundaries, with the kv-bytes
    telemetry flowing."""
    cfg, model, params = gpt2_setup
    rng = np.random.RandomState(11)
    trace = [(rng.randint(1, 120, (p,)).astype(np.int32), m)
             for p, m in [(5, 6), (15, 5), (9, 4)]]
    eng = _assert_engine_exact(model, params, trace, cfg.eos_token_id,
                               num_slots=3, block_size=4, num_blocks=40,
                               prefill_chunk=8, max_model_len=64,
                               gather_buckets=[16, 64], kernel="pallas")
    assert eng.kernel == "pallas"
    slo = eng.slo_summary()
    assert slo["kernel"] == "pallas" and slo["kv_dtype"] == "fp"
    assert slo["kv_bytes_read_per_step"] > 0
    assert eng.stats().kv_bytes_read > 0


def test_engine_exact_int8_pools_under_preemption(gpt2_setup):
    """int8 KV pools (the removed rejection): engine output is
    token-exact vs generate_causal on the SAME int8-cache config,
    including under forced recompute preemption — quantization is
    deterministic, so the re-prefilled pools are bitwise identical."""
    cfg, model, params = gpt2_setup
    int8 = _int8_model(model, cfg)
    rng = np.random.RandomState(12)
    trace = [(rng.randint(1, 120, (9,)).astype(np.int32), 18)
             for _ in range(4)]
    eng = _assert_engine_exact(int8, params, trace, cfg.eos_token_id,
                               num_slots=4, block_size=4, num_blocks=10,
                               prefill_chunk=8, max_model_len=32)
    assert eng.stats().preemptions > 0
    assert eng.kv_cache_dtype == "int8"
    # int8 + fp32-scale pools cost fewer bytes/token than fp pools
    fp_eng = _assert_engine_exact(model, params, [trace[0]],
                                  cfg.eos_token_id, num_slots=1,
                                  block_size=4, num_blocks=10,
                                  prefill_chunk=8, max_model_len=32)
    assert eng.blocks.token_bytes < fp_eng.blocks.token_bytes


def test_engine_int8_composes_with_speculative_and_prefix(gpt2_setup):
    """int8 pools through BOTH riders: the draft/verify window path
    (scale planes scatter with the window writes, rewind hides stale
    scales with stale values) and prefix-cache sharing (shared blocks
    carry int8 + scales; a primed template re-serves exactly)."""
    cfg, model, params = gpt2_setup
    int8 = _int8_model(model, cfg)
    rng = np.random.RandomState(13)
    trace = [(rng.randint(1, 120, (p,)).astype(np.int32), m)
             for p, m in [(5, 8), (9, 6), (7, 7)]]
    eng = _assert_engine_exact(int8, params, trace, cfg.eos_token_id,
                               num_slots=2, block_size=4, num_blocks=60,
                               prefill_chunk=8, max_model_len=64,
                               speculate_k=3, draft=1)
    assert {str(p.dtype) for p in eng._d_pools} == {"int8", "float32"}
    assert eng.stats().draft_proposed > 0

    from huggingface_sagemaker_tensorflow_distributed_tpu.serve.engine import (
        ServeEngine,
    )

    shared = rng.randint(1, 120, (12,)).astype(np.int32)
    tails = [(np.concatenate([shared,
                              rng.randint(1, 120, (t,)).astype(np.int32)]),
              5) for t in (3, 5, 2)]
    eng2 = ServeEngine(int8, params, num_slots=3, block_size=4,
                       num_blocks=40, prefill_chunk=8, max_model_len=64,
                       prefix_cache=True)
    eng2.submit(shared, 1)
    eng2.run()                            # prime the template
    reqs = [eng2.submit(p, m) for p, m in tails]
    eng2.run()
    for (p, m), r in zip(tails, reqs):
        got = [int(t) for t in eng2.output_ids(r)]
        assert got == _reference(int8, params, p, m, cfg.eos_token_id)
    assert eng2.blocks.peak_shared_blocks > 0


def test_engine_serves_sliding_window_llama():
    """The removed sliding-window rejection: a Mistral-style windowed
    GQA config serves token-exact vs its own generate_causal (the
    window bands from logical positions on the gathered path)."""
    import jax.numpy as jnp

    from huggingface_sagemaker_tensorflow_distributed_tpu.models.auto import (
        init_params,
    )
    from huggingface_sagemaker_tensorflow_distributed_tpu.models.llama import (
        LlamaConfig,
        LlamaForCausalLM,
    )

    cfg = LlamaConfig(vocab_size=128, hidden_size=32, num_layers=2,
                      num_heads=4, num_kv_heads=2, intermediate_size=64,
                      max_position_embeddings=128, eos_token_id=127,
                      pad_token_id=0, dtype=jnp.float32,
                      sliding_window=12)
    model = LlamaForCausalLM(cfg)
    params = init_params(model, cfg, seed=0)
    rng = np.random.RandomState(14)
    # continuations push contexts PAST the window so banding engages
    trace = [(rng.randint(3, 120, (p,)).astype(np.int32), m)
             for p, m in [(6, 10), (11, 8)]]
    _assert_engine_exact(model, params, trace, cfg.eos_token_id,
                         num_slots=2, block_size=8, num_blocks=20,
                         prefill_chunk=8, max_model_len=64)


def test_engine_sliding_window_pallas_int8_llama():
    """The full ISSUE 9 composition on the hardest config: windowed
    GQA Llama served through the fused kernel over int8 pools — the
    kernel's banded tile-skip, GQA grouping, and in-tile dequant all
    engaged at once, still token-exact vs generate_causal on the
    matching int8 config."""
    import dataclasses

    import jax.numpy as jnp

    from huggingface_sagemaker_tensorflow_distributed_tpu.models.auto import (
        init_params,
    )
    from huggingface_sagemaker_tensorflow_distributed_tpu.models.llama import (
        LlamaConfig,
        LlamaForCausalLM,
    )

    cfg = LlamaConfig(vocab_size=128, hidden_size=32, num_layers=2,
                      num_heads=4, num_kv_heads=2, intermediate_size=64,
                      max_position_embeddings=128, eos_token_id=127,
                      pad_token_id=0, dtype=jnp.float32,
                      sliding_window=12)
    model = LlamaForCausalLM(cfg)
    params = init_params(model, cfg, seed=0)
    int8 = LlamaForCausalLM(dataclasses.replace(cfg,
                                                kv_cache_dtype="int8"))
    rng = np.random.RandomState(15)
    trace = [(rng.randint(3, 120, (p,)).astype(np.int32), m)
             for p, m in [(6, 10), (11, 8)]]
    eng = _assert_engine_exact(int8, params, trace, cfg.eos_token_id,
                               num_slots=2, block_size=8, num_blocks=20,
                               prefill_chunk=8, max_model_len=64,
                               kernel="pallas", gather_buckets=[24, 64])
    assert eng.kernel == "pallas" and eng.kv_cache_dtype == "int8"


def test_kv_pool_bytes_doubles_int8_admission(gpt2_setup):
    """The capacity-accounting satellite: pools sized by the SAME byte
    budget hold ~2x (with scale overhead, >=2x at D=16... exactly
    token_bytes-proportionally) more blocks under int8 — and through
    the scheduler's block-denominated admission math, more resident
    requests — instead of inheriting fp-sized reservations."""
    cfg, model, params = gpt2_setup
    int8 = _int8_model(model, cfg)
    rng = np.random.RandomState(16)
    trace = [(rng.randint(1, 120, (8,)).astype(np.int32), 8)
             for _ in range(6)]
    budget = None
    from huggingface_sagemaker_tensorflow_distributed_tpu.serve.engine import (
        ServeEngine,
    )

    probe = ServeEngine(model, params, num_slots=6, block_size=4,
                        num_blocks=8, prefill_chunk=8, max_model_len=32)
    # budget = exactly 5 fp blocks' worth of pool bytes
    budget = 5 * probe.blocks.block_bytes
    fp_eng = _assert_engine_exact(model, params, trace,
                                  cfg.eos_token_id, num_slots=6,
                                  block_size=4, prefill_chunk=8,
                                  max_model_len=32,
                                  kv_pool_bytes=budget)
    int8_eng = _assert_engine_exact(int8, params, trace,
                                    cfg.eos_token_id, num_slots=6,
                                    block_size=4, prefill_chunk=8,
                                    max_model_len=32,
                                    kv_pool_bytes=budget)
    assert fp_eng.blocks.num_blocks == 6          # 1 + 5
    assert int8_eng.blocks.num_blocks >= 2 * fp_eng.blocks.num_blocks - 1
    assert (int8_eng.stats().peak_resident_requests
            >= 2 * fp_eng.stats().peak_resident_requests)


_SEEN = dict(platform="tpu", pool_kinds=("kv", "kv"), mesh=False,
             head_dim=128, kv_dtype="fp")
# a latent-attention plan as the engine sees it: one pool a layer, the
# "head size" its row width (640 for the published 576)
_LATENT = {"pool_kinds": ("latent", "latent"), "head_dim": 640}


@pytest.mark.parametrize("kernel,seen,want", [
    # left to choose: a kernel where it was measured to win ...
    (None, {}, "paged_kernel"),
    (None, _LATENT, "paged_kernel"),
    # ... and the gather path for anything else the engine can see
    (None, {"platform": "cpu"}, "gather"),
    (None, {"platform": "gpu"}, "gather"),
    (None, {**_LATENT, "platform": "cpu"}, "gather"),
    (None, {"pool_kinds": ("kv", "latent")}, "gather"),
    (None, {"pool_kinds": ("kv", "latent"), "head_dim": 640}, "gather"),
    (None, {"mesh": True}, "gather"),
    (None, {**_LATENT, "mesh": True}, "gather"),
    (None, {"head_dim": 64}, "gather"),
    (None, {**_LATENT, "head_dim": 576}, "gather"),
    (None, {"kv_dtype": "int8"}, "gather"),
    (None, {**_LATENT, "kv_dtype": "int8"}, "gather"),
    # an explicit value wins, on any platform
    ("xla", {}, "gather"),
    ("xla", _LATENT, "gather"),
    ("pallas", {"platform": "cpu"}, "paged_kernel"),
    ("pallas", {"platform": "cpu", "head_dim": 64, "kv_dtype": "int8"},
     "paged_kernel"),
    # ... a latent-attention model with routed experts among them (the
    # paged step counts the pairs as the gather step does: ISSUE 34)
    ("pallas", {**_LATENT, "platform": "cpu"}, "paged_kernel"),
    # ... except where no kernel has a form
    ("pallas", {"pool_kinds": ("kv", "latent")}, "mixes pool kinds"),
    ("pallas", {"mesh": True}, "tensor-parallel"),
    ("pallas", {**_LATENT, "mesh": True}, "tensor-parallel"),
])
def test_decode_path_is_a_function_of_what_the_engine_sees(kernel, seen,
                                                           want):
    from huggingface_sagemaker_tensorflow_distributed_tpu.serve.engine import (
        resolve_decode_path,
    )

    if want in ("paged_kernel", "gather"):
        assert resolve_decode_path(kernel, **{**_SEEN, **seen}) == want
    else:
        with pytest.raises(ValueError, match=want):
            resolve_decode_path(kernel, **{**_SEEN, **seen})


def test_llama_default_heads_take_the_kernel_inside_its_byte_bound():
    """The chooser does not ask how many KV heads a pool has: the
    kernel's compute block is bounded in bytes, so ``LlamaConfig``'s own
    default (Llama-2-7B's 32 KV heads of 128, no grouping), which the
    first form of PR 29's kernel could not compile for the v5e (16.6 MiB
    of VMEM), takes the kernel with a block of 128 keys, 1 MiB a pool."""
    from huggingface_sagemaker_tensorflow_distributed_tpu.models.llama import (
        LlamaConfig,
    )
    from huggingface_sagemaker_tensorflow_distributed_tpu.ops.pallas_paged_attention import (
        block_pages,
    )
    from huggingface_sagemaker_tensorflow_distributed_tpu.serve.engine import (
        resolve_decode_path,
    )

    cfg = LlamaConfig()
    kv_heads, head_dim = cfg.num_kv_heads, cfg.hidden_size // cfg.num_heads
    assert (kv_heads, head_dim) == (32, 128)
    assert resolve_decode_path(
        None, **{**_SEEN, "head_dim": head_dim}) == "paged_kernel"
    pages = block_pages(16, kv_heads, head_dim, 2, 4000)
    assert pages == 8 and pages * 16 * kv_heads * head_dim * 2 == 1 << 20


@pytest.mark.parametrize("kernel,path", [(None, "gather"),
                                         ("pallas", "paged_kernel")])
def test_decode_path_is_on_the_span_and_in_the_stats(gpt2_setup, tmp_path,
                                                     kernel, path):
    """The path a decode step took is one line of a traced run away: the
    ``serve/decode_step`` span's arguments, ``stats()`` with the steps
    counted by path, ``slo_summary()`` and the ``report`` event. Left to
    choose on a CPU the engine keeps the gather path."""
    import json

    from huggingface_sagemaker_tensorflow_distributed_tpu import obs
    from huggingface_sagemaker_tensorflow_distributed_tpu.serve.engine import (
        ServeEngine,
    )

    cfg, model, params = gpt2_setup
    rng = np.random.RandomState(13)
    obs.reset(out_dir=str(tmp_path / "telemetry"), enabled=True)
    try:
        eng = ServeEngine(model, params, num_slots=2, block_size=4,
                          num_blocks=40, prefill_chunk=8, max_model_len=32,
                          kernel=kernel)
        for n in (5, 9):
            eng.submit(rng.randint(1, 120, (n,)).astype(np.int32), 4)
        eng.run()
        st = eng.stats()
        obs.flush()
        with open(tmp_path / "telemetry" / "events.jsonl") as f:
            events = [json.loads(line) for line in f]
    finally:
        obs.reset(enabled=False)
    other = ({"paged_kernel", "gather"} - {path}).pop()
    assert eng.decode_path == st.decode_path == path
    assert st.decode_steps_by_path == {path: st.decode_steps, other: 0}
    assert st.decode_steps > 0
    assert eng.slo_summary()["decode_path"] == path
    spans = [e for e in events if e.get("type") == "span"
             and e["name"] == "serve/decode_step"]
    assert spans and all(e["args"]["decode_path"] == path for e in spans)
    report = [e for e in events if e.get("event") == "report"][-1]
    assert report["decode_path"] == path
    assert report["kernel"] == ("pallas" if path == "paged_kernel"
                                else "xla")


def test_parse_kernel_and_kv_dtype_knobs(monkeypatch):
    from huggingface_sagemaker_tensorflow_distributed_tpu.serve.engine import (
        ENV_KERNEL,
        ENV_KV_DTYPE,
        parse_kernel,
        parse_kv_dtype,
    )

    monkeypatch.delenv(ENV_KERNEL, raising=False)
    assert parse_kernel(None) is None          # unset: the engine chooses
    assert parse_kernel("XLA") == "xla"
    assert parse_kernel("PALLAS") == "pallas"
    monkeypatch.setenv(ENV_KERNEL, "pallas")
    assert parse_kernel(None) == "pallas"
    with pytest.raises(ValueError, match="xla | pallas"):
        parse_kernel("triton")
    assert parse_kv_dtype(None, "fp") == "fp"
    assert parse_kv_dtype(None, "int8") == "int8"
    assert parse_kv_dtype("int8", "fp") == "int8"
    monkeypatch.setenv(ENV_KV_DTYPE, "int8")
    assert parse_kv_dtype(None, "fp") == "int8"
    with pytest.raises(ValueError, match="fp | int8"):
        parse_kv_dtype("fp16", "fp")


# -- dispatch-ahead serving loop (ISSUE 12) ----------------------------------

def _run_overlap_pair(model, params, trace, kws=None, **engine_kw):
    """Serve the same trace twice — ``overlap`` off then on — and
    return (off_outputs, on_outputs, on_engine). The exactness torture
    harness: the pipelined loop must be semantically invisible."""
    from huggingface_sagemaker_tensorflow_distributed_tpu.serve.engine import (
        ServeEngine,
    )

    kws = kws or [dict() for _ in trace]
    outs = {}
    engines = {}
    for mode in ("off", "on"):
        eng = ServeEngine(model, params, overlap=mode, **engine_kw)
        reqs = [eng.submit(p, m, **kw) for (p, m), kw in zip(trace, kws)]
        eng.run()
        outs[mode] = [[int(t) for t in eng.output_ids(r)] for r in reqs]
        engines[mode] = eng
    assert engines["off"].overlap_flushes == 0    # serial never drains
    return outs["off"], outs["on"], engines["on"]


def test_overlap_exact_with_eos_on_inflight_iteration(gpt2_setup):
    """EOS lands while the next iteration is already in flight (the
    dispatch-ahead loop discovers a finish one step LATE and must
    discard the wasted in-flight token): rebuild the model so EOS is a
    token the reference actually emits mid-stream, serve a multi-slot
    trace, and require overlap-on output == overlap-off output ==
    generate_causal, token for token."""
    import dataclasses

    cfg, model, params = gpt2_setup
    rng = np.random.RandomState(21)
    prompts = [rng.randint(1, 120, (p,)).astype(np.int32)
               for p in (5, 9, 12, 7)]
    # EOS = the 3rd greedy continuation token of prompt 0: that request
    # finishes mid-decode with other slots still running, so the finish
    # is always discovered with a dispatch in flight
    ref = _reference(model, params, prompts[0], 12, eos=-1)
    eos_cfg = dataclasses.replace(cfg, eos_token_id=int(ref[2]))
    eos_model = type(model)(eos_cfg)
    trace = [(p, 12) for p in prompts]
    off, on, eng = _run_overlap_pair(
        eos_model, params, trace, num_slots=4, block_size=4,
        num_blocks=60, prefill_chunk=8, max_model_len=64)
    assert on == off
    assert eng.overlap
    for (p, m), got in zip(trace, on):
        assert got == _reference(eos_model, params, p, m,
                                 eos_cfg.eos_token_id)


def test_overlap_exact_across_bucket_switches(gpt2_setup):
    """Bucket grow mid-pipeline: contexts crossing the 16-wide first
    bucket while dispatches are in flight — the bucket choice is
    re-derived from exact counts (context advances at dispatch), so
    the switch needs no flush and changes no tokens."""
    cfg, model, params = gpt2_setup
    rng = np.random.RandomState(22)
    trace = [(rng.randint(1, 120, (p,)).astype(np.int32), 9)
             for p in (15, 16, 17, 5)]
    off, on, eng = _run_overlap_pair(
        model, params, trace, num_slots=4, block_size=4, num_blocks=60,
        prefill_chunk=8, max_model_len=64, gather_buckets=[16, 32])
    assert on == off
    assert eng.bucket_switches > 0          # the ladder really moved
    assert eng.overlap_flushes == 0         # growth is count-derived


def test_overlap_exact_under_forced_preemption_and_flushes(gpt2_setup):
    """The mandatory flush: KV pressure / preemption must act on
    committed state, so the pipeline drains first (overlap_flushes
    latches it) and recompute preemption stays token-invisible."""
    cfg, model, params = gpt2_setup
    rng = np.random.RandomState(1)
    trace = [(rng.randint(1, 120, (9,)).astype(np.int32), 18)
             for _ in range(5)]
    off, on, eng = _run_overlap_pair(
        model, params, trace, num_slots=4, block_size=4, num_blocks=10,
        prefill_chunk=8, max_model_len=32)
    assert on == off
    assert eng.stats().preemptions > 0
    assert eng.overlap_flushes > 0          # the drain was mandatory
    assert eng.stats().overlap_flushes == eng.overlap_flushes


def test_overlap_sampled_bitwise_and_spec_rejection_storm(gpt2_setup,
                                                          spec_draft):
    """The remaining torture axes in one composition: (a) sampled
    streams stay bitwise identical across the pipeline (fold indices
    re-derived through the in-flight count), and (b) a speculative
    engine under an adversarial draft (rejection storm) + tight-pool
    preemption — where the window commit is the pipeline boundary —
    is token-identical with overlap on vs off."""
    cfg, model, params = gpt2_setup
    rng = np.random.RandomState(23)
    trace = [(rng.randint(1, 120, (9,)).astype(np.int32), 14)
             for _ in range(4)]
    kws = [dict(temperature=0.9, top_k=20, top_p=0.9, seed=s)
           for s in (1, 2, 3)] + [dict()]
    off, on, _ = _run_overlap_pair(
        model, params, trace, kws=kws, num_slots=3, block_size=4,
        num_blocks=40, prefill_chunk=8, max_model_len=32)
    assert on == off                        # bitwise, greedy rider too
    # speculative rejection storm + preemption, overlap on vs off
    off_s, on_s, eng = _run_overlap_pair(
        model, params, trace, num_slots=4, block_size=4, num_blocks=11,
        prefill_chunk=8, max_model_len=32, speculate_k=2,
        draft=spec_draft)
    assert on_s == off_s
    stats = eng.stats()
    assert stats.preemptions > 0
    assert 0 <= stats.acceptance_rate < 1   # rejections actually hit
    assert (eng.blocks.num_free + eng.blocks.num_cached
            == eng.blocks.num_blocks - 1)
    assert eng.blocks.num_used == 0


def test_generated_tail_registers_resubmit_hits_cache(gpt2_setup):
    """PR 7a follow-up: a finished request's GENERATED tail joins the
    prefix index, so agentic multi-turn traffic that re-submits its
    own completion as the next prompt hits the cache past the original
    prompt — exactness vs a cold generate_causal + a nonzero hit rate
    covering generated blocks are both required."""
    from huggingface_sagemaker_tensorflow_distributed_tpu.serve.engine import (
        ServeEngine,
    )

    cfg, model, params = gpt2_setup
    rng = np.random.RandomState(24)
    prompt = rng.randint(1, 120, (12,)).astype(np.int32)
    eng = ServeEngine(model, params, num_slots=2, block_size=4,
                      num_blocks=40, prefill_chunk=4, max_model_len=64)
    first = eng.submit(prompt, 12)
    eng.run()
    out1 = eng.output_ids(first)
    assert len(out1) == 12                  # no EOS: full continuation
    # the agentic turn: the client folds its completion into the next
    # prompt. blocks_for(prompt+output minus the partial tail) of the
    # FIRST request's blocks are now indexed — including generated
    # ones past the 12-token prompt
    follow = np.concatenate([prompt, out1]).astype(np.int32)
    second = eng.submit(follow, 6)
    eng.run()
    got = [int(t) for t in eng.output_ids(second)]
    assert got == _reference(model, params, follow, 6, cfg.eos_token_id)
    # the cached span covers GENERATED tokens: more than the original
    # prompt's full blocks were served from cache
    assert second.prefix_cached_tokens > (len(prompt) // 4) * 4
    assert second.cache_hit_rate > 0
    assert eng.stats().cache_hit_rate > 0


def test_generated_tail_registration_is_partial_block_safe(gpt2_setup):
    """Only FULL aligned blocks of the finished sequence are
    published: a short continuation that never completes a block adds
    nothing to the index (and the conservation invariant holds with
    the finished request's blocks parked in the cache LRU)."""
    from huggingface_sagemaker_tensorflow_distributed_tpu.serve.engine import (
        ServeEngine,
    )

    cfg, model, params = gpt2_setup
    rng = np.random.RandomState(25)
    prompt = rng.randint(1, 120, (8,)).astype(np.int32)
    eng = ServeEngine(model, params, num_slots=2, block_size=4,
                      num_blocks=40, prefill_chunk=4, max_model_len=64)
    req = eng.submit(prompt, 2)             # ctx 9: blocks 0..1 full
    eng.run()
    # full blocks of (prompt + 2 generated)[:9] = 2; both indexable
    assert eng.blocks.num_cached == 2
    assert (eng.blocks.num_free + eng.blocks.num_cached
            == eng.blocks.num_blocks - 1)
    assert eng.blocks.num_used == 0
    assert req.rid in eng.finished


def test_parse_overlap_knob(monkeypatch):
    from huggingface_sagemaker_tensorflow_distributed_tpu.serve.engine import (
        ENV_OVERLAP,
        parse_overlap,
    )

    assert parse_overlap(None) is True      # default on
    assert parse_overlap("off") is False
    assert parse_overlap("on") is True
    assert parse_overlap(False) is False
    monkeypatch.setenv(ENV_OVERLAP, "off")
    assert parse_overlap(None) is False
    monkeypatch.setenv(ENV_OVERLAP, "1")
    assert parse_overlap(None) is True
    with pytest.raises(ValueError, match=ENV_OVERLAP):
        parse_overlap("sometimes")


# -- ISSUE 13: tensor-parallel serving engine --------------------------------

def _run_tp_pair(model, params, trace, tp=2, **engine_kw):
    """Serve the same trace on a single-device engine and a TP-mesh
    engine (the 8-fake-CPU-device conftest backend); returns
    (base_outputs, tp_outputs, tp_engine). The tentpole gate: sharding
    must be semantically invisible — token-identical output."""
    from huggingface_sagemaker_tensorflow_distributed_tpu.serve.engine import (
        ServeEngine,
    )

    outs = {}
    engines = {}
    for mesh in (None, tp):
        eng = ServeEngine(model, params, mesh=mesh, **engine_kw)
        reqs = [eng.submit(p, m) for p, m in trace]
        eng.run()
        outs[mesh] = [[int(t) for t in eng.output_ids(r)] for r in reqs]
        engines[mesh] = eng
    assert engines[None].tp == 1 and engines[None].mesh is None
    assert engines[tp].tp == tp and engines[tp].mesh is not None
    return outs[None], outs[tp], engines[tp]


def test_tp_engine_token_exact_across_bucket_boundary(gpt2_setup,
                                                      devices8):
    """The ISSUE 13 tier-1 exactness gate, half 1: a TP=2 engine
    (params Megatron-sharded, every KV pool sharded on heads) emits
    token-identical output to the TP=1 engine across a gather-bucket
    boundary — and its per-device KV accounting is half the model's."""
    cfg, model, params = gpt2_setup
    rng = np.random.RandomState(31)
    # contexts cross the 16-wide first bucket mid-decode
    trace = [(rng.randint(1, 120, (p,)).astype(np.int32), m)
             for p, m in [(5, 9), (15, 6), (12, 8)]]
    base, tp, eng = _run_tp_pair(
        model, params, trace, num_slots=3, block_size=4, num_blocks=40,
        prefill_chunk=8, max_model_len=32, gather_buckets=[16, 32])
    assert tp == base
    assert eng.bucket_switches > 0          # the boundary really moved
    # per-device re-denomination: each of the 2 shards holds half the
    # heads, so bytes/token halves vs the model's own figure
    # (num_layers × K+V × hidden × 4 bytes fp32)
    assert eng.blocks.token_bytes * 2 == \
        cfg.num_layers * 2 * cfg.hidden_size * 4
    slo = eng.slo_summary()
    assert slo["tp"] == 2
    assert slo["kv_pool_bytes_per_device"] == eng.blocks.pool_bytes


def test_tp_engine_token_exact_under_forced_preemption(gpt2_setup,
                                                       devices8):
    """The ISSUE 13 tier-1 exactness gate, half 2: recompute
    preemption on the sharded engine — re-prefill over sharded pools
    reproduces the stream exactly, and the per-device byte figure is
    half the single-device engine's on the same geometry."""
    cfg, model, params = gpt2_setup
    rng = np.random.RandomState(1)
    trace = [(rng.randint(1, 120, (9,)).astype(np.int32), 18)
             for _ in range(5)]
    base, tp, eng = _run_tp_pair(
        model, params, trace, num_slots=4, block_size=4, num_blocks=10,
        prefill_chunk=8, max_model_len=32)
    assert tp == base
    assert eng.stats().preemptions > 0
    assert eng.stats().tp == 2
    # same block geometry, half the bytes per device
    from huggingface_sagemaker_tensorflow_distributed_tpu.serve.engine import (
        ServeEngine,
    )

    lone = ServeEngine(model, params, num_slots=4, block_size=4,
                       num_blocks=10, prefill_chunk=8, max_model_len=32)
    assert eng.blocks.token_bytes * 2 == lone.blocks.token_bytes
    assert eng.blocks.pool_bytes * 2 == lone.blocks.pool_bytes


@pytest.mark.slow
def test_tp_engine_speculative_prefix_int8_composition(gpt2_setup,
                                                       devices8):
    """The sharded engine under ALL the riders at once (ISSUE 13 slow
    tier): speculative draft/verify (draft pools sharded over the same
    mesh), copy-on-write prefix caching (shard-local block copies),
    and int8 pools (scale pools shard on their heads axis too) —
    token-identical to the same composition single-device."""
    cfg, model, params = gpt2_setup
    int8 = _int8_model(model, cfg)
    rng = np.random.RandomState(33)
    shared = rng.randint(1, 120, (8,)).astype(np.int32)
    trace = [(np.concatenate([shared,
                              rng.randint(1, 120, (t,)).astype(np.int32)]),
              6) for t in (5, 3, 4, 6)]
    base, tp, eng = _run_tp_pair(
        model, params, trace, num_slots=3, block_size=4, num_blocks=60,
        prefill_chunk=8, max_model_len=48, speculate_k=2, draft=1,
        prefix_cache=True, kv_cache_dtype="int8")
    assert tp == base
    stats = eng.stats()
    assert stats.tp == 2
    assert stats.draft_proposed > 0
    assert stats.prefix_cached_tokens > 0   # the template really hit
    assert {str(p.dtype) for p in eng._pools} == {"int8", "float32"}
    # the draft's pools shard like the target's
    assert eng._d_plan.kv_shardings and eng._plan.kv_shardings


@pytest.mark.slow
def test_tp_sampled_serve_seed_deterministic_across_preemption(
        gpt2_setup, devices8):
    """ISSUE 13 acceptance, sampled half: streams on the SHARDED
    engine are bitwise seed-reproducible — a rerun with identical
    seeds reproduces identical tokens, and tight-pool recompute
    preemption changes nothing. (Cross-sharding identity is a GREEDY
    contract only: TP's row-parallel reductions reorder float sums, so
    sampled warp thresholds may differ in ulps between TP degrees —
    what is gated here is determinism OF the sharded engine.)"""
    from huggingface_sagemaker_tensorflow_distributed_tpu.serve.engine import (
        ServeEngine,
    )

    cfg, model, params = gpt2_setup
    rng = np.random.RandomState(35)
    trace = [(rng.randint(1, 120, (9,)).astype(np.int32), 14)
             for _ in range(4)]
    kws = [dict(temperature=0.9, top_k=20, top_p=0.9, seed=s)
           for s in (1, 2, 3)] + [dict()]

    def run(num_blocks):
        eng = ServeEngine(model, params, mesh=2, num_slots=3,
                          block_size=4, num_blocks=num_blocks,
                          prefill_chunk=8, max_model_len=32)
        reqs = [eng.submit(p, m, **kw) for (p, m), kw in zip(trace, kws)]
        eng.run()
        return [[int(t) for t in eng.output_ids(r)] for r in reqs], eng

    base, eng = run(40)
    assert eng.tp == 2
    again, _ = run(40)
    assert again == base                    # bitwise reproducible
    tight, teng = run(9)                    # tight pool: preemption
    assert teng.stats().preemptions > 0
    assert tight == base                    # preemption-invariant


def test_tp_engine_rejections_and_knob(gpt2_setup, devices8,
                                       monkeypatch):
    """The loud-rejection contracts: non-dividing kv heads (GQA), the
    pallas kernel, and the ``HSTD_SERVE_TP`` parsing rules."""
    import jax.numpy as jnp

    from huggingface_sagemaker_tensorflow_distributed_tpu.models.auto import (
        init_params,
    )
    from huggingface_sagemaker_tensorflow_distributed_tpu.models.llama import (
        LlamaConfig,
        LlamaForCausalLM,
    )
    from huggingface_sagemaker_tensorflow_distributed_tpu.serve.engine import (
        ENV_TP,
        ServeEngine,
        parse_tp,
    )

    cfg, model, params = gpt2_setup
    kw = dict(num_slots=2, block_size=4, num_blocks=20, prefill_chunk=8,
              max_model_len=32)
    # GQA: it is the KV heads that must divide — 2 kv heads cannot
    # shard over tensor=4
    lcfg = LlamaConfig(vocab_size=128, hidden_size=32, num_layers=2,
                       num_heads=4, num_kv_heads=2, intermediate_size=64,
                       max_position_embeddings=128, eos_token_id=127,
                       pad_token_id=0, dtype=jnp.float32)
    lmodel = LlamaForCausalLM(lcfg)
    lparams = init_params(lmodel, lcfg, seed=0)
    with pytest.raises(ValueError, match="kv heads"):
        ServeEngine(lmodel, lparams, mesh=4, **kw)
    # ... and the SAME config serves fine at tp=2 (kv heads divide)
    eng = ServeEngine(lmodel, lparams, mesh=2, **kw)
    assert eng.tp == 2
    with pytest.raises(ValueError, match="pallas"):
        ServeEngine(model, params, mesh=2, kernel="pallas", **kw)
    # knob parsing
    assert parse_tp(None) == 1
    assert parse_tp(2) == 2
    assert parse_tp("4") == 4
    monkeypatch.setenv(ENV_TP, "2")
    assert parse_tp(None) == 2
    monkeypatch.setenv(ENV_TP, "")
    assert parse_tp(None) == 1
    with pytest.raises(ValueError, match=ENV_TP):
        parse_tp("two")
    with pytest.raises(ValueError, match=ENV_TP):
        parse_tp(0)


# -- ISSUE 13 satellite: low-load dispatch-ahead auto-flush ------------------

def test_overlap_lone_stream_auto_flushes_to_serial(gpt2_setup,
                                                    monkeypatch):
    """PR 12 follow-up: with decode occupancy 1 and an empty queue the
    dispatch-ahead pipeline auto-flushes — a lone stream commits every
    token in the iteration that dispatched it (no one-iteration
    deferred fetch on any token, and no trailing drain iteration), so
    last-token latency matches ``overlap='off'`` structurally:
    identical iteration count, identical tokens, zero pipeline
    dispatches. Telemetry elsewhere is unchanged — a concurrent trace
    still engages the pipeline (control below)."""
    from huggingface_sagemaker_tensorflow_distributed_tpu.serve.engine import (
        ServeEngine,
    )

    cfg, model, params = gpt2_setup
    rng = np.random.RandomState(34)
    prompt = rng.randint(1, 120, (9,)).astype(np.int32)
    kw = dict(num_slots=3, block_size=4, num_blocks=40, prefill_chunk=8,
              max_model_len=64)
    calls = []
    orig = ServeEngine._dispatch_decode
    monkeypatch.setattr(ServeEngine, "_dispatch_decode",
                        lambda self: (calls.append(1), orig(self))[1])
    off = ServeEngine(model, params, overlap=False, **kw)
    r_off = off.submit(prompt, 8)
    off.run()
    on = ServeEngine(model, params, overlap=True, **kw)
    r_on = on.submit(prompt, 8)
    on.run()
    assert not calls                        # never pipelined
    assert on.overlap and on.overlap_flushes == 0
    # last-token latency parity: the pipelined loop would need one
    # extra iteration to drain the final in-flight dispatch
    assert on.iterations == off.iterations
    assert list(on.output_ids(r_on)) == list(off.output_ids(r_off))
    # control: occupancy > 1 re-engages the pipeline, tokens unchanged
    calls.clear()
    trace = [(rng.randint(1, 120, (7,)).astype(np.int32), 6)
             for _ in range(3)]
    off2, on2, eng2 = _run_overlap_pair(model, params, trace, **kw)
    assert on2 == off2
    assert calls                            # dispatch-ahead really ran


# -- ISSUE 17: KV host tier (swap preemption + prefix demotion) --------------

def test_extract_insert_blocks_roundtrip_bitwise():
    """The tentpole's standalone unit gate: ``extract_blocks`` /
    ``insert_blocks`` round-trip a block set bitwise — value pools AND
    int8-style scale pools travel atomically — into the SAME or
    DIFFERENT destination ids, and the pair never touches the
    BlockManager (no refcount or free-list movement: pool I/O and
    block accounting are separate layers by design)."""
    import jax.numpy as jnp

    from huggingface_sagemaker_tensorflow_distributed_tpu.serve.paged_kv import (
        extract_blocks,
        insert_blocks,
    )

    rng = np.random.RandomState(17)
    nb, bs = 12, 4
    # an int8-mode pool family: int8 values + fp32 scale planes
    pools = (
        jnp.asarray(rng.randn(nb, bs, 2, 3).astype(np.float32)),
        jnp.asarray(rng.randint(-128, 128, (nb, bs, 2, 3), np.int32)
                    .astype(np.int8)),
        jnp.asarray(rng.randn(nb, bs, 2).astype(np.float32)),
    )
    d_pools = (jnp.asarray(rng.randn(nb, bs, 2, 3).astype(np.float32)),)
    before = [np.asarray(p) for p in pools]

    bm = BlockManager(num_blocks=nb, block_size=bs)
    src = bm.allocate(3)
    free0, used0 = bm.num_free, bm.num_used
    snapshot = list(bm._free)

    bset = extract_blocks(pools, src, d_pools=d_pools)
    assert bset.n_blocks == 3 and bset.nbytes > 0
    # scatter into different ids on zeroed pools: bitwise per block
    dst = [b for b in range(1, nb) if b not in src][:3]
    zero = tuple(jnp.zeros_like(p) for p in pools)
    zero_d = tuple(jnp.zeros_like(p) for p in d_pools)
    out, out_d = insert_blocks(zero, bset, dst, d_pools=zero_d)
    for pi, p in enumerate(out):
        got = np.asarray(p)
        for s, d in zip(src, dst):
            np.testing.assert_array_equal(got[d], before[pi][s])
            assert got[d].dtype == before[pi][s].dtype
        # untouched rows stay zero
        other = [b for b in range(nb) if b not in dst]
        assert not np.asarray(p)[other].any()
    for s, d in zip(src, dst):
        np.testing.assert_array_equal(
            np.asarray(out_d[0])[d], np.asarray(d_pools[0])[s])
    # round-trip into the SAME ids reproduces the original pools
    back, _ = insert_blocks(zero, bset, src, d_pools=zero_d)
    for pi, p in enumerate(back):
        for s in src:
            np.testing.assert_array_equal(np.asarray(p)[s], before[pi][s])
    # the manager never moved: extraction is not an eviction
    assert (bm.num_free, bm.num_used) == (free0, used0)
    assert list(bm._free) == snapshot
    bm.release(src)
    assert bm.num_used == 0
    # shape mismatches are loud
    with pytest.raises(ValueError):
        insert_blocks(zero, bset, dst[:2])
    with pytest.raises(ValueError):
        insert_blocks(zero, bset, dst)      # draft payloads, no d_pools


def _run_swap(model, params, trace, swap, kws=None, swap_bytes=None,
              **engine_kw):
    from huggingface_sagemaker_tensorflow_distributed_tpu.serve.engine import (
        ServeEngine,
    )

    kws = kws or [dict() for _ in trace]
    eng = ServeEngine(model, params, swap=swap, swap_bytes=swap_bytes,
                      **engine_kw)
    reqs = [eng.submit(p, m, **kw) for (p, m), kw in zip(trace, kws)]
    eng.run()
    return [[int(t) for t in eng.output_ids(r)] for r in reqs], eng


def test_swap_preemption_token_exact_greedy(gpt2_setup):
    """The ISSUE 17 exactness gate, greedy arm: on the forced-preemption
    trace a swapped-and-restored request is token-identical to the
    recompute path AND to generate_causal (= the unpreempted answer),
    with overlap ON and the pipeline provably drained before every
    extraction; the swap path really ran (outs/ins/tokens-avoided all
    positive) and a starved byte budget falls back to recompute, still
    exact."""
    cfg, model, params = gpt2_setup
    rng = np.random.RandomState(1)
    trace = [(rng.randint(1, 120, (9,)).astype(np.int32), 18)
             for _ in range(5)]
    kw = dict(num_slots=4, block_size=4, num_blocks=10, prefill_chunk=8,
              max_model_len=32)
    swp, eng = _run_swap(model, params, trace, "always", **kw)
    rec, rec_eng = _run_swap(model, params, trace, "never", **kw)
    assert swp == rec
    for (p, m), got in zip(trace, swp):
        assert got == _reference(model, params, p, m, cfg.eos_token_id)
    st = eng.stats()
    assert st.preemptions > 0 and rec_eng.stats().preemptions > 0
    assert st.swap_outs > 0 and st.swap_ins > 0
    assert st.recompute_tokens_avoided > 0 and st.swap_bytes > 0
    assert st.swap_policy == "always"
    assert rec_eng.stats().swap_outs == 0   # never = recompute arm
    # overlap pipeline drained before extraction (the default loop ran)
    assert eng.overlap and eng.overlap_flushes > 0
    # conservation after the run: swap freed what it extracted
    assert eng.blocks.num_used == 0
    assert (eng.blocks.num_free + eng.blocks.num_cached
            + eng.blocks.num_hosted == eng.blocks.num_blocks - 1)
    # a 1-byte budget can never reserve a set: recompute fallback, exact
    starved, s_eng = _run_swap(model, params, trace, "always",
                               swap_bytes=1, **kw)
    assert starved == swp
    assert s_eng.stats().swap_outs == 0
    assert s_eng.stats().preemptions > 0


def test_swap_sampled_bitwise_and_auto_policy(gpt2_setup):
    """Sampled arm: seeded streams under swap preemption are BITWISE
    identical to the roomy-pool unpreempted run (swap keeps the
    request's emitted output intact, so fold indices never shift), and
    ``auto`` stays exact while actually exercising its estimate — on
    this geometry a victim's few KV blocks are far cheaper to move
    than the weight reads its re-prefill would stream, so auto picks
    the swap arm."""
    cfg, model, params = gpt2_setup
    rng = np.random.RandomState(9)
    trace = [(rng.randint(1, 120, (9,)).astype(np.int32), 14)
             for _ in range(4)]
    kws = [dict(temperature=0.9, top_k=20, top_p=0.9, seed=s)
           for s in (1, 2, 3)] + [dict()]
    base, _ = _run_swap(model, params, trace, "off", kws=kws,
                        num_slots=3, block_size=4, num_blocks=40,
                        prefill_chunk=8, max_model_len=32)
    tight, teng = _run_swap(model, params, trace, "always", kws=kws,
                            num_slots=3, block_size=4, num_blocks=9,
                            prefill_chunk=8, max_model_len=32)
    assert teng.stats().preemptions > 0 and teng.stats().swap_outs > 0
    assert tight == base                    # bitwise, greedy rider too
    auto, aeng = _run_swap(model, params, trace, "auto", kws=kws,
                           num_slots=3, block_size=4, num_blocks=9,
                           prefill_chunk=8, max_model_len=32)
    assert auto == base
    assert aeng.stats().swap_policy == "auto"
    # 2 * set_bytes << param_bytes * prefill_dispatches here: the
    # estimate picks swap, and the telemetry names the avoided work
    assert aeng.stats().swap_outs > 0
    assert aeng.stats().recompute_tokens_avoided > 0


def test_swap_preemption_exact_int8_pools(gpt2_setup):
    """int8 arm: the scale planes travel with the value blocks, so a
    swapped int8 request restores bitwise and stays token-exact vs
    generate_causal on the int8-cache config."""
    cfg, model, params = gpt2_setup
    int8 = _int8_model(model, cfg)
    rng = np.random.RandomState(12)
    trace = [(rng.randint(1, 120, (9,)).astype(np.int32), 18)
             for _ in range(4)]
    kw = dict(num_slots=4, block_size=4, num_blocks=10, prefill_chunk=8,
              max_model_len=32)
    swp, eng = _run_swap(int8, params, trace, "always", **kw)
    rec, _ = _run_swap(int8, params, trace, "never", **kw)
    assert swp == rec
    for (p, m), got in zip(trace, swp):
        assert got == _reference(int8, params, p, m, cfg.eos_token_id)
    assert eng.stats().swap_outs > 0
    assert eng.kv_cache_dtype == "int8"


def test_prefix_demotion_revives_instead_of_recomputing(gpt2_setup):
    """The demotion tier: two templates alternating over a pool that
    holds only one — evict-only (swap='off') pays a cold miss every
    swing, the tier ('never': demote active, recompute preemption)
    revives demoted blocks from host and keeps the hit rate up, tokens
    identical."""
    cfg, model, params = gpt2_setup
    rng = np.random.RandomState(31)
    t1 = rng.randint(1, 120, (16,)).astype(np.int32)
    t2 = rng.randint(1, 120, (16,)).astype(np.int32)
    trace = []
    for _ in range(6):
        for t in (t1, t2):
            tail = rng.randint(1, 120, (2,)).astype(np.int32)
            trace.append((np.concatenate([t, tail]), 3))
    kw = dict(num_slots=1, block_size=4, num_blocks=8, prefill_chunk=8,
              max_model_len=32)
    off, off_eng = _run_swap(model, params, trace, "off", **kw)
    tier, tier_eng = _run_swap(model, params, trace, "never", **kw)
    assert tier == off
    off_hit = off_eng.stats().cache_hit_rate or 0.0
    tier_hit = tier_eng.stats().cache_hit_rate or 0.0
    assert tier_hit > off_hit               # revives beat cold misses
    st = tier_eng.stats()
    assert st.host_tier_hits > 0
    assert st.host_tier_hit_rate and 0 < st.host_tier_hit_rate <= 1
    assert off_eng.stats().host_tier_hit_rate is None  # off: field absent
    # host-tier state drains clean: every hosted block still conserved
    bm = tier_eng.blocks
    assert (bm.num_free + bm.num_used + bm.num_cached + bm.num_hosted
            == bm.num_blocks - 1)


def test_parse_swap_knobs(monkeypatch):
    from huggingface_sagemaker_tensorflow_distributed_tpu.serve.engine import (
        ENV_SWAP,
        ENV_SWAP_BYTES,
        parse_swap,
        parse_swap_bytes,
    )

    assert parse_swap(None) == "off"        # default: tier fully off
    for mode in ("auto", "always", "never", "off"):
        assert parse_swap(mode) == mode
    monkeypatch.setenv(ENV_SWAP, "always")
    assert parse_swap(None) == "always"
    with pytest.raises(ValueError, match=ENV_SWAP):
        parse_swap("sometimes")

    assert parse_swap_bytes(None) is None   # unbounded
    assert parse_swap_bytes(0) is None      # 0 = unbounded too
    assert parse_swap_bytes(1 << 20) == 1 << 20
    assert parse_swap_bytes("4096") == 4096
    monkeypatch.setenv(ENV_SWAP_BYTES, "2048")
    assert parse_swap_bytes(None) == 2048
    with pytest.raises(ValueError, match=ENV_SWAP_BYTES):
        parse_swap_bytes(-1)


def test_revive_survives_budget_eviction_during_reservation():
    """Regression (found by the bench's budgeted run): an admission
    that matched host-tier payloads must not lose them to its OWN
    allocations. ``_reserve``'s revive-block / private-block allocates
    can evict cached blocks, and spilling those under a FULL host
    budget evicts payloads oldest-first — which is exactly where the
    matched (still LRU-cold, peek mutates nothing) entries sit.
    Unpinned, ``revive_hosted`` KeyErrors; pinned, the in-flight
    demotions drop instead (a demoted prefix is an opportunity, a
    matched one a commitment) and the revival lands."""
    from types import SimpleNamespace

    bm = BlockManager(num_blocks=10, block_size=4)
    s = Scheduler(2, bm, 4, 16, prefix_cache=True)
    # budget = exactly two 64-byte payloads: demoting anything further
    # must evict oldest-first
    bm.set_spill(lambda b: SimpleNamespace(nbytes=64), host_budget=128)

    # park prefix A (2 full blocks) host-side ONLY: register, release,
    # demote both payloads (budget now full), then reclaim the demoted
    # device ids so a future match is host-tier-or-nothing
    tokens_a = np.arange(1, 9).astype(np.int32)
    ta = bm.allocate(2)
    bm.register_prefix(tokens_a, ta)
    bm.release(ta)
    assert bm.demote(max_blocks=2) == 2
    held = bm.allocate(7) + bm.allocate(2)   # 2nd call reclaims hosted
    assert bm.num_hosted == 0 and bm.num_free == 0
    # refill the LRU with OTHER registered prefixes (3 x 2 blocks) so
    # the admission below must evict-and-spill to allocate at all
    for lo in (20, 40, 60):
        t = held[:2]
        held = held[2:]
        bm.register_prefix(np.arange(lo, lo + 8).astype(np.int32), t)
        bm.release(t)
    assert bm.num_cached == 6 and bm.num_free == 0

    # admission: prompt = prefix A + one fresh block. peek_hosted
    # matches A's 2 keys; the 3 needed allocations each evict + spill
    # a cached block against the full budget
    s.submit(Request(prompt=np.concatenate(
        [tokens_a, np.arange(100, 104).astype(np.int32)]),
        max_new_tokens=2))
    [slot] = s.admit()
    assert bm.host_tier_hits == 2
    assert len(slot.pending_restores) == 2
    assert slot.prefill_pos == 8             # revived spans skipped
    # the matched payloads survived; the in-flight demotions were
    # dropped, not queued behind them
    assert bm.host_evictions == 0
    assert (bm.num_free + bm.num_used + bm.num_cached
            + bm.num_hosted == bm.num_blocks - 1)
