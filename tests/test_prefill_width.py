"""Prefill at the gather ladder's width (ISSUE 26): a prefill dispatch
attends the smallest bucket that holds ``max(start) + C`` over its
rows, not ``max_model_len``. The gates: the jitted program at a bucket
against the full-width one on the same pools; the engine token for
token against ``generate_causal`` on both sides of the first bucket
(plain, as a prefix-cache hit, through preemption-resume); no compile
after ``warmup()`` and exactly ``len(ladder) + 1`` prefill programs; a
lone row past the first bucket rides the batched shape and is charged
for it; and the two counters that say how full the buckets ran."""

import json

import jax.numpy as jnp
import numpy as np
import pytest

from huggingface_sagemaker_tensorflow_distributed_tpu import obs
from huggingface_sagemaker_tensorflow_distributed_tpu.models.auto import (
    init_params,
)
from huggingface_sagemaker_tensorflow_distributed_tpu.models.generate import (
    generate_causal,
)
from huggingface_sagemaker_tensorflow_distributed_tpu.serve import engine as E
from huggingface_sagemaker_tensorflow_distributed_tpu.serve.engine import (
    ServeEngine,
)

C = 8            # prefill chunk of every engine here
GEOM = dict(block_size=4, prefill_chunk=C)


def _gpt2():
    from huggingface_sagemaker_tensorflow_distributed_tpu.models.gpt2 import (
        Gpt2Config,
        Gpt2LMHeadModel,
    )

    cfg = Gpt2Config(vocab_size=128, hidden_size=32, num_layers=2,
                     num_heads=2, intermediate_size=64,
                     max_position_embeddings=128, hidden_dropout=0.0,
                     embd_dropout=0.0, attention_dropout=0.0,
                     eos_token_id=127, pad_token_id=0, dtype=jnp.float32)
    return cfg, Gpt2LMHeadModel(cfg)


def _llama_gqa():
    from huggingface_sagemaker_tensorflow_distributed_tpu.models.llama import (
        LlamaConfig,
        LlamaForCausalLM,
    )

    cfg = LlamaConfig(vocab_size=128, hidden_size=32, num_layers=2,
                      num_heads=4, num_kv_heads=2, intermediate_size=64,
                      max_position_embeddings=128, eos_token_id=127,
                      pad_token_id=0, dtype=jnp.float32)
    return cfg, LlamaForCausalLM(cfg)


@pytest.fixture(scope="module", params=["gpt2", "llama_gqa"])
def setup(request):
    cfg, model = {"gpt2": _gpt2, "llama_gqa": _llama_gqa}[request.param]()
    return cfg, model, init_params(model, cfg, seed=0)


@pytest.fixture(scope="module")
def gpt2_setup():
    cfg, model = _gpt2()
    return cfg, model, init_params(model, cfg, seed=0)


def _reference(model, params, prompt, max_new, eos):
    ref = [int(t) for t in np.asarray(generate_causal(
        model, params, jnp.asarray(prompt)[None],
        max_new_tokens=max_new))[0]]
    return ref[:ref.index(eos) + 1] if eos in ref else ref


def _record_dispatches(eng):
    """Wrap the engine's jitted prefill so that every dispatch leaves
    its (rows, width) behind."""
    seen, fn = [], eng._prefill_fn

    def recording(*args):
        seen.append((args[3].shape[0], args[14]))
        return fn(*args)

    eng._prefill_fn = recording
    return seen


def _run_exact(model, params, trace, eos, **engine_kw):
    eng = ServeEngine(model, params, **GEOM, **engine_kw)
    seen = _record_dispatches(eng)
    reqs = [eng.submit(p, m) for p, m in trace]
    eng.run()
    for (prompt, max_new), req in zip(trace, reqs):
        assert ([int(t) for t in eng.output_ids(req)]
                == _reference(model, params, prompt, max_new, eos)), \
            f"request {req.rid} diverged (preemptions={req.preemptions})"
    return eng, seen


# -- (a) the program at a bucket against the full-width one -------------------

@pytest.mark.parametrize("width,starts", [
    (16, [0, 0, 0, 0]),          # start = 0
    (16, [8, 8, 8, 8]),          # start = bucket - C
    (32, [0, 24, 8, 16]),        # rows of mixed start
], ids=["start0", "start_bucket_minus_C", "mixed_start"])
def test_prefill_chunk_at_a_bucket_equals_full_width(setup, width, starts):
    cfg, model, params = setup
    eng = ServeEngine(model, params, num_slots=4, num_blocks=80,
                      max_model_len=64, **GEOM)
    rng = np.random.RandomState(width + sum(starts))
    # pools that hold something at every position: what a row attends
    # before `start` is then real context, what lies past it real junk
    pools = [jnp.asarray(rng.standard_normal(p.shape), p.dtype)
             for p in eng._pools]
    G, nb = 4, eng.max_blocks_per_seq
    tables = (1 + np.arange(G * nb, dtype=np.int32)).reshape(G, nb)
    zf, zi = np.zeros((G,), np.float32), np.zeros((G,), np.int32)
    args = (model, params, pools,
            rng.randint(1, 120, (G, C)).astype(np.int32), tables,
            np.asarray(starts, np.int32), np.full((G,), C - 1, np.int32),
            zf, zi, zf, np.zeros((G, 2), np.uint32), zi, eng._plan, False)
    fn = E._prefill_chunk_jit(False)
    tok_full, pools_full = fn(*args, None)
    tok, pools_at = fn(*args, width)
    assert list(np.asarray(tok)) == list(np.asarray(tok_full))
    for got, want in zip(pools_at, pools_full):
        # the dropped columns are exact zeros after the softmax, so
        # only the grouping of an fp32 reduction may differ
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)
    # and the chunk was really written (the pools changed where the
    # tables point, nowhere else)
    changed = np.any(np.asarray(pools_at[0]) != np.asarray(pools[0]),
                     axis=(1, 2, 3))
    want_blocks = {int(tables[g, (s // 4) + j])
                   for g, s in enumerate(starts) for j in range(C // 4)}
    assert set(np.flatnonzero(changed)) == want_blocks


def test_prefill_chunk_rejects_a_width_off_the_block_grid(gpt2_setup):
    cfg, model, params = gpt2_setup
    eng = ServeEngine(model, params, num_slots=2, num_blocks=40,
                      max_model_len=64, **GEOM)
    G, nb = 1, eng.max_blocks_per_seq
    zf, zi = np.zeros((G,), np.float32), np.zeros((G,), np.int32)
    with pytest.raises(ValueError, match="multiple"):
        E._prefill_chunk(model, params, eng._pools,
                         np.zeros((G, C), np.int32),
                         np.zeros((G, nb), np.int32), zi, zi, zf, zi, zf,
                         np.zeros((G, 2), np.uint32), zi, eng._plan, False,
                         10)


# -- (b) the engine against generate_causal around the first bucket ----------

# padded lengths one chunk under, at, and one chunk over a 16-wide
# first bucket (chunk 8): 8, 16, 24
AROUND_BUCKET = (7, 15, 20)


def test_engine_exact_around_the_first_bucket_plain(setup):
    cfg, model, params = setup
    rng = np.random.RandomState(31)
    trace = [(rng.randint(1, 120, (p,)).astype(np.int32), 5)
             for p in AROUND_BUCKET]
    eng, seen = _run_exact(model, params, trace, cfg.eos_token_id,
                           num_slots=3, num_blocks=60, max_model_len=64,
                           gather_buckets=[16], prefix_cache="off")
    assert eng.gather_buckets == [16, 64]
    # chunks that start at 0 and 8 ran at the first bucket, the chunk
    # that starts at 16 at the next, and that one as a full batch
    assert {w for _, w in seen} == {16, 64}
    assert all(g == eng.prefill_batch for g, w in seen if w == 64)


def test_engine_exact_around_the_first_bucket_prefix_hit(gpt2_setup):
    """A cache hit starts its prefill at ``start > 0``: the bucket
    counts the cached prefix, which the chunk must attend."""
    cfg, model, params = gpt2_setup
    rng = np.random.RandomState(32)
    prefix = rng.randint(1, 120, (16,)).astype(np.int32)
    tails = [rng.randint(1, 120, (t,)).astype(np.int32) for t in (3, 7, 2)]
    # the first request registers the 16-token prefix and keeps
    # running; the riders hit it and start at 8 or 16
    trace = [(np.concatenate([prefix, t]), m)
             for t, m in zip(tails, (12, 4, 4))]
    trace.append((prefix[:12].copy(), 3))
    eng, seen = _run_exact(model, params, trace, cfg.eos_token_id,
                           num_slots=2, num_blocks=60, max_model_len=64,
                           gather_buckets=[16])
    hits = [r.prefix_cached_tokens for r in eng.finished.values()]
    assert sum(1 for h in hits if h > 0) >= 2
    assert {w for _, w in seen} == {16, 64}
    assert eng.prefill_keys_needed <= eng.prefill_keys_attended


def test_engine_exact_through_preemption_resume_past_the_bucket(gpt2_setup):
    """Recompute preemption folds the generated tokens into the
    prompt: the resumed prefill's ``start`` passes the first bucket and
    its rows ride the batched shape at the next."""
    cfg, model, params = gpt2_setup
    rng = np.random.RandomState(1)
    trace = [(rng.randint(1, 120, (9,)).astype(np.int32), 18)
             for _ in range(5)]
    eng, seen = _run_exact(model, params, trace, cfg.eos_token_id,
                           num_slots=4, num_blocks=10, max_model_len=32,
                           gather_buckets=[16], prefix_cache="off")
    assert eng.stats().preemptions > 0
    assert {w for _, w in seen} == {16, 32}
    assert all(g == eng.prefill_batch for g, w in seen if w == 32)


# -- (c) compile flatness and the programs warmed -----------------------------

@pytest.mark.parametrize("buckets,ladder", [
    ([16], [16, 64]), ([16, 32], [16, 32, 64]), ("full", [64]),
], ids=["two_buckets", "three_buckets", "full"])
def test_warmup_covers_every_dispatchable_prefill_program(
        gpt2_setup, tmp_path, buckets, ladder):
    cfg, model, params = gpt2_setup
    obs.reset(out_dir=str(tmp_path / "telemetry"), enabled=True)
    try:
        eng = ServeEngine(model, params, num_slots=4, num_blocks=80,
                          max_model_len=64, gather_buckets=buckets,
                          prefix_cache="off", **GEOM)
        assert eng.gather_buckets == ladder
        warmed = _record_dispatches(eng)
        eng.warmup()
        # the batched shape at every bucket, the lone-row shape at the
        # first only: len(ladder) + 1 programs (2 under "full", as
        # before there was a ladder)
        programs = [(1, ladder[0])] + [(eng.prefill_batch, w)
                                       for w in ladder]
        assert sorted(warmed) == sorted(programs)
        del warmed[:]
        count0 = obs.compile_tracker().count
        rng = np.random.RandomState(33)

        def serve(lengths):
            for n in lengths:
                eng.submit(rng.randint(1, 120, (n,)).astype(np.int32), 3)
            while eng.has_work():
                eng.step()

        serve([7])                   # a lone row under the first bucket
        serve([20])                  # a lone row that passes it
        serve([7, 15, 15, 7])        # a full batch under it
        serve([20, 28, 20, 28])      # a full batch past it
        serve([36])                  # and past a second bucket
        assert obs.compile_tracker().count == count0, \
            "a prefill dispatch compiled after warmup()"
        # the traffic reached every program, and no other exists
        assert set(warmed) == set(programs)
        obs.flush()
        spans = {}
        with open(tmp_path / "telemetry" / "events.jsonl") as f:
            for e in map(json.loads, f):
                if e.get("type") == "span":
                    spans[e["name"]] = e
    finally:
        obs.reset()
    assert {"serve/warmup/prefill_g1", "serve/warmup/prefill_g4"} <= set(spans)
    assert spans["serve/warmup/prefill_g4"]["parent"] == "serve/warmup"
    for w in ladder:
        child = spans[f"serve/warmup/prefill_g4/w{w}"]
        assert child["parent"] == "serve/warmup/prefill_g4"


# -- (d) a lone row past the first bucket -------------------------------------

def test_lone_row_past_the_first_bucket_rides_the_batch_and_pays_for_it(
        gpt2_setup):
    cfg, model, params = gpt2_setup
    eng = ServeEngine(model, params, num_slots=4, num_blocks=80,
                      max_model_len=64, gather_buckets=[16],
                      prefix_cache="off", **GEOM)
    seen = _record_dispatches(eng)
    prompt = np.arange(1, 37, dtype=np.int32)        # pads to 5 chunks
    req = eng.submit(prompt, 2)
    eng.warmup()
    del seen[:]
    eng.step()
    # an empty 4-slot engine's budget is 4 chunks: two lone-row
    # dispatches at the first bucket take one each, the third chunk
    # (start 16) leaves that bucket, rides the batched shape and is
    # charged its 4 rows, so the iteration stops there (charged one
    # row, it would have dispatched a fourth)
    assert seen == [(1, 16), (1, 16), (eng.prefill_batch, 64)]
    assert eng.prefill_batch == 4
    assert eng.prefill_chunks == 3 and eng.prefill_dispatches == 3
    assert eng.prefill_keys_needed == 8 + 16 + 24
    assert eng.prefill_keys_attended == 16 + 16 + 4 * 64
    eng.run()
    assert ([int(t) for t in eng.output_ids(req)]
            == _reference(model, params, prompt, 2, cfg.eos_token_id))


# -- (e) the counters on the ledger -------------------------------------------

def test_ledger_lines_hold_the_prefill_key_counters(gpt2_setup, tmp_path):
    cfg, model, params = gpt2_setup
    out = tmp_path / "telemetry"
    obs.reset(out_dir=str(out), enabled=True)
    try:
        eng = ServeEngine(model, params, num_slots=3, num_blocks=60,
                          max_model_len=64, gather_buckets=[16], **GEOM)
        rng = np.random.RandomState(34)
        for n in (7, 15, 20, 28, 7):
            eng.submit(rng.randint(1, 120, (n,)).astype(np.int32), 4)
        eng.run()
        obs.flush()
        events = [e for _, e, err in obs.iter_events(
            str(out / "events.jsonl")) if err is None]
    finally:
        obs.reset()
    ledgers = [e for e in events if e.get("event") == "iteration_ledger"]
    assert len(ledgers) == eng.iterations
    for e in ledgers:
        assert 0 <= e["prefill_keys_needed"] <= e["prefill_keys_attended"]
        assert (e["prefill_keys_attended"] > 0) == (
            e["prefill_dispatches"] > 0)
    assert sum(e["prefill_keys_needed"] for e in ledgers) \
        == eng.prefill_keys_needed == eng.stats().prefill_keys_needed > 0
    assert sum(e["prefill_keys_attended"] for e in ledgers) \
        == eng.prefill_keys_attended == eng.stats().prefill_keys_attended
    report = [e for e in events if e.get("event") == "report"][-1]
    assert report["prefill_keys_needed"] == eng.prefill_keys_needed
    assert report["prefill_keys_attended"] == eng.prefill_keys_attended
    assert report["prefill_dispatches"] == eng.prefill_dispatches
    spans = [e for e in events if e.get("type") == "span"
             and e["name"] == "serve/prefill_chunk"]
    assert len(spans) == eng.prefill_dispatches
    assert sum(e["args"]["rows"] * e["args"]["width"] for e in spans) \
        == eng.prefill_keys_attended
