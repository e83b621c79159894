"""A latent-attention model with dropless routed experts through the
serving engine (ISSUE 28): token for token against ``generate_causal``
(plain, with a prefix-cache hit and its copy-on-write, swapped out and in,
preempted and resumed) on latent pools, by the gather step and by the
paged step whose attention is the fused latent kernel (ISSUE 34:
``kernel="pallas"``, interpret mode here; the step a TPU takes by
itself); the pools, the plan and the programs of a K/V model left as they
were; what the engine refuses; and the routed counters on the ledger,
the same by either step."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from huggingface_sagemaker_tensorflow_distributed_tpu import obs
from huggingface_sagemaker_tensorflow_distributed_tpu.models import (
    deepseek_v2 as D,
)
from huggingface_sagemaker_tensorflow_distributed_tpu.models.generate import (
    generate_causal,
)
from huggingface_sagemaker_tensorflow_distributed_tpu.serve import engine as E
from huggingface_sagemaker_tensorflow_distributed_tpu.serve.engine import (
    ServeEngine,
)
from huggingface_sagemaker_tensorflow_distributed_tpu.serve.paged_kv import (
    extract_blocks,
    insert_blocks,
)

GEOM = dict(block_size=4, prefill_chunk=8, max_model_len=64)

# the decode step by both paths: left to choose on a CPU the engine
# gathers; ``pallas`` is the paged step a TPU takes for latent pools
BOTH_PATHS = pytest.mark.parametrize(
    "kernel,path", [(None, "gather"), ("pallas", "paged_kernel")],
    ids=["gather", "paged_kernel"])


@pytest.fixture(scope="module")
def latent():
    cfg = D.DeepseekV2Config(
        vocab_size=128, hidden_size=32, num_layers=3, num_heads=2,
        q_lora_rank=12, kv_lora_rank=8, qk_nope_head_dim=8,
        qk_rope_head_dim=4, v_head_dim=8, intermediate_size=48,
        moe_intermediate_size=16, n_routed_experts=8, n_shared_experts=1,
        num_experts_per_tok=2, n_group=4, topk_group=2, experts_held=4,
        max_position_embeddings=128, eos_token_id=127, pad_token_id=0)
    model = D.DeepseekV2ForCausalLM(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.ones((1, 8), jnp.int32))["params"]
    return cfg, model, params


def _reference(model, params, prompt, max_new, eos=127):
    ref = [int(t) for t in np.asarray(generate_causal(
        model, params, jnp.asarray(prompt)[None],
        max_new_tokens=max_new))[0]]
    return ref[:ref.index(eos) + 1] if eos in ref else ref


def _prompts(seed, lengths):
    rng = np.random.RandomState(seed)
    return [rng.randint(1, 120, (n,)).astype(np.int32) for n in lengths]


def _serve_exact(model, params, trace, **kw):
    eng = ServeEngine(model, params, **{**GEOM, **kw})
    reqs = [eng.submit(p, m) for p, m in trace]
    eng.run()
    for (p, m), req in zip(trace, reqs):
        assert ([int(t) for t in eng.output_ids(req)]
                == _reference(model, params, p, m)), \
            f"request {req.rid} diverged (preemptions={req.preemptions})"
    return eng


@BOTH_PATHS
@pytest.mark.parametrize("overlap", ["on", "off"])
@pytest.mark.parametrize("buckets", [None, "full"], ids=["ladder", "full"])
def test_engine_is_generate_causal_token_for_token(latent, overlap, buckets,
                                                   kernel, path):
    cfg, model, params = latent
    trace = [(p, 9) for p in _prompts(1, (5, 23, 40, 17, 33, 9))]
    eng = _serve_exact(model, params, trace, num_slots=4, num_blocks=80,
                       overlap=overlap, gather_buckets=buckets,
                       kernel=kernel)
    st = eng.stats()
    assert st.preemptions == 0 and st.decode_steps > 0
    other = ({"paged_kernel", "gather"} - {path}).pop()
    assert st.decode_steps_by_path == {path: st.decode_steps, other: 0}
    # one pool a layer, rows with no heads axis
    assert len(eng._pools) == cfg.num_layers
    assert all(p.shape == (80, 4, 128) for p in eng._pools)
    assert st.latent_bytes_per_token == 3 * 128 * 4 == st.kv_token_bytes
    assert [k[0] for k in eng._plan.kinds].count("latent") == 3


@BOTH_PATHS
def test_prefix_hit_and_copy_on_write_stay_exact(latent, kernel, path):
    """The recipe of ``test_serve.py``'s forced-COW gate: a long request,
    then short riders over its 12-token prefix admitted while it still
    holds its blocks; blocks of 4 under chunks of 8 re-align the cached
    prefix to the chunk, so a rider that diverges mid-chunk must
    privatise the overlap block before scattering into it."""
    cfg, model, params = latent
    prefix = _prompts(2, (12,))[0]
    tails = _prompts(3, (3, 1, 2, 1, 2))
    trace = [(np.concatenate([prefix, t]), m)
             for t, m in zip(tails, (14, 2, 4, 3, 4))]
    trace[1] = (prefix.copy(), 2)               # the prompt IS the prefix
    eng = _serve_exact(model, params, trace, num_slots=2, num_blocks=40,
                       max_model_len=32, kernel=kernel)
    st = eng.stats()
    assert st.decode_path == path
    assert st.prefix_cached_tokens > 0 and st.cache_hit_rate > 0
    assert st.cow_copies > 0 and st.blocks_shared_peak > 0
    assert eng.blocks.num_used == 0


@BOTH_PATHS
def test_swap_out_and_in_stays_exact(latent, kernel, path):
    cfg, model, params = latent
    trace = [(p, 18) for p in _prompts(4, (9, 9, 9, 9, 9))]
    eng = _serve_exact(model, params, trace, num_slots=4, num_blocks=10,
                       max_model_len=32, swap="always", kernel=kernel)
    st = eng.stats()
    assert st.decode_path == path
    assert st.preemptions > 0 and st.swap_outs > 0 and st.swap_ins > 0
    assert eng.blocks.num_used == 0


def test_extract_and_insert_round_trip_bitwise(latent):
    cfg, model, params = latent
    eng = ServeEngine(model, params, num_slots=2, num_blocks=20, **GEOM)
    req = eng.submit(_prompts(5, (14,))[0], 3)
    eng.run()
    ids = [b for b in range(1, 20)
           if float(jnp.abs(eng._pools[0][b]).max()) > 0][:3]
    assert len(ids) == 3
    before = [np.asarray(p) for p in eng._pools]
    bset = extract_blocks(eng._pools, ids)
    assert bset.n_blocks == 3
    assert bset.signature[0] == (((4, 128), "float32"),) * 3
    wiped = [p.at[jnp.asarray(ids)].set(0) for p in eng._pools]
    back, _ = insert_blocks(wiped, bset, ids)
    for a, b in zip(before, back):
        np.testing.assert_array_equal(a, np.asarray(b))
    # and into other blocks: the payload lands where it is put
    moved, _ = insert_blocks(wiped, bset, [17, 18, 19])
    np.testing.assert_array_equal(np.asarray(moved[1][17]), before[1][ids[0]])


@BOTH_PATHS
def test_preemption_and_recompute_resume_stay_exact(latent, kernel, path):
    cfg, model, params = latent
    trace = [(p, 18) for p in _prompts(6, (9, 9, 9, 9, 9))]
    eng = _serve_exact(model, params, trace, num_slots=4, num_blocks=10,
                       max_model_len=32, kernel=kernel)
    assert eng.stats().decode_path == path
    assert eng.stats().preemptions > 0 and eng.stats().swap_outs == 0


# -- a K/V model is served as it was -------------------------------------------

def _llama():
    from huggingface_sagemaker_tensorflow_distributed_tpu.models.llama import (
        LlamaConfig,
        LlamaForCausalLM,
    )

    cfg = LlamaConfig(vocab_size=128, hidden_size=32, num_layers=2,
                      num_heads=4, num_kv_heads=2, intermediate_size=64,
                      max_position_embeddings=128, eos_token_id=127,
                      pad_token_id=0, dtype=jnp.float32, qkv_bias=True,
                      model_type="qwen2")
    model = LlamaForCausalLM(cfg)
    return cfg, model, model.init(jax.random.PRNGKey(0),
                                  jnp.ones((1, 8), jnp.int32))["params"]


def test_a_kv_models_plan_pools_and_programs_are_unchanged():
    cfg, model, params = _llama()
    plan, pool_shapes = E.build_cache_plan(model, params, 64)
    assert plan.kinds == (("index",), ("kv", 0), ("kv", 1),
                          ("index",), ("kv", 2), ("kv", 3), ("scalar",))
    assert pool_shapes == [(2, 8, jnp.float32)] * 4
    assert E.pool_dims(plan, pool_shapes, 30, 4) == [(30, 4, 2, 8)] * 4
    eng = ServeEngine(model, params, num_slots=4, num_blocks=30, **GEOM)
    assert [p.shape for p in eng._pools] == [(30, 4, 2, 8)] * 4
    assert eng.blocks.token_bytes == 4 * 2 * 8 * 4
    seen = []
    pre, dec = eng._prefill_fn, eng._decode_fn
    eng._prefill_fn = lambda *a: seen.append(
        ("prefill", a[3].shape[0], a[14])) or pre(*a)
    eng._decode_fn = lambda *a: seen.append(("decode", a[13])) or dec(*a)
    eng.warmup()
    # the programs of warm-up: [1, C] at the first bucket, [4, C] at
    # both, decode at both (and its device-token feed, the same program)
    assert sorted(set(seen)) == [("decode", 16), ("decode", 64),
                                 ("prefill", 1, 16), ("prefill", 4, 16),
                                 ("prefill", 4, 64)]
    assert len([s for s in seen if s[0] == "prefill"]) == 3
    # a step returns what it always returned: next tokens and pools
    out = E._decode_step_jit(False)(
        model, params, eng._pools, np.zeros((4,), np.int32),
        np.zeros((4, 16), np.int32), np.zeros((4,), np.int32),
        np.zeros((4,), bool), np.zeros((4,), np.float32),
        np.zeros((4,), np.int32), np.zeros((4,), np.float32),
        np.zeros((4, 2), np.uint32), np.zeros((4,), np.int32),
        eng._plan, 16, False)
    assert len(out) == 2
    st = eng.stats()
    assert st.latent_bytes_per_token is None and st.moe_pairs == 0
    assert not eng._latent and not eng._routes


# -- what the engine refuses ---------------------------------------------------

def test_latent_pool_under_tensor_parallelism_is_refused(latent, devices8):
    from huggingface_sagemaker_tensorflow_distributed_tpu.parallel.mesh import (
        tensor_parallel_mesh,
    )

    cfg, model, params = latent
    with pytest.raises(ValueError, match="no\\s+heads axis to shard"):
        E.build_cache_plan(model, params, 32, mesh=tensor_parallel_mesh(2))


@pytest.mark.parametrize("kernel", [None, "pallas"])
def test_speculation_is_refused(latent, kernel):
    """By either decode path (the paged step itself builds: the
    exactness tests above run on it)."""
    cfg, model, params = latent
    with pytest.raises(ValueError, match="speculative decoding is not"):
        ServeEngine(model, params, speculate_k=2, kernel=kernel, **GEOM)


def test_capacity_slot_experts_are_still_refused_with_the_reason():
    from huggingface_sagemaker_tensorflow_distributed_tpu.models.llama import (
        LlamaConfig,
        LlamaForCausalLM,
    )

    cfg = LlamaConfig(vocab_size=64, hidden_size=16, num_layers=1,
                      num_heads=2, num_kv_heads=2, intermediate_size=16,
                      num_experts=4, max_position_embeddings=64)
    model = LlamaForCausalLM(cfg)
    with pytest.raises(ValueError, match="capacity") as e:
        ServeEngine(model, None)
    assert "Dropless routed experts" in str(e.value)


# -- the routed counters -------------------------------------------------------

def _events(tmp_path):
    with open(tmp_path / "telemetry" / "events.jsonl") as f:
        return [json.loads(line) for line in f]


def _counted_run(model, params, tmp_path, prompts, **kw):
    """The prompts, nine tokens each, through an engine under a sink:
    (stats, events)."""
    obs.reset(out_dir=str(tmp_path / "telemetry"), enabled=True)
    try:
        eng = ServeEngine(model, params, num_slots=4, num_blocks=80,
                          **kw, **GEOM)
        for p in prompts:
            eng.submit(p, 9)
        eng.run()
        st = eng.stats()
        obs.flush()
        return st, _events(tmp_path)
    finally:
        obs.reset(enabled=False)


@BOTH_PATHS
@pytest.mark.parametrize("overlap", ["on", "off"])
def test_ledger_carries_the_routed_counts(latent, tmp_path, overlap, kernel,
                                          path):
    cfg, model, params = latent
    prompts = _prompts(8, (5, 23, 40, 17, 33, 9))
    st, events = _counted_run(model, params, tmp_path, prompts,
                              overlap=overlap, kernel=kernel)
    lines = [e for e in events if e.get("event") == "iteration_ledger"]
    assert lines and all("moe_pairs" in e for e in lines)
    for e in lines:
        assert 0 <= e["moe_pairs_held"] <= e["moe_pairs"]
        assert 0 <= e["moe_decode_pairs_held"] <= e["moe_decode_pairs"]
        assert e["moe_decode_pairs"] <= e["moe_pairs"]
        assert e["dur_s"] + 1e-5 >= (e["stage_s"] + e["dispatch_s"]
                                     + e["fetch_wait_s"] + e["commit_s"])
        if "moe_experts_touched" in e:
            assert len(e["moe_experts_touched"]) == cfg.num_moe_layers == 2
            assert all(0 <= t <= cfg.held for t in e["moe_experts_touched"])
            assert all(mx >= mean for mx, mean in zip(
                e["moe_expert_load_max"], e["moe_expert_load_mean"]))
    # every real token made k x expert-layers pairs, pads and pad rows none:
    # prompt tokens + the decode steps' tokens (the first token of a request
    # comes out of its prefill)
    tokens = sum(len(p) for p in prompts) + 6 * 8
    assert st.moe_pairs == tokens * 2 * 2
    assert 0 < st.moe_pairs_held < st.moe_pairs
    assert sum(e["moe_pairs"] for e in lines) <= st.moe_pairs
    report = [e for e in events if e.get("event") == "report"][-1]
    assert report["moe_pairs"] == st.moe_pairs
    assert report["latent_bytes_per_token"] == st.latent_bytes_per_token
    spans = [e for e in events if e.get("type") == "span"]
    paths = {(e["name"], (e.get("args") or {}).get("latent_path"),
              (e.get("args") or {}).get("decode_path"))
             for e in spans if e["name"] in ("serve/prefill_chunk",
                                             "serve/decode_step")}
    assert paths == {("serve/prefill_chunk", "expanded", None),
                     ("serve/decode_step", "absorbed", path)}
    assert report["decode_path"] == st.decode_path == path


_MOE_FIELDS = ("moe_pairs", "moe_pairs_held", "moe_decode_pairs",
               "moe_decode_pairs_held", "moe_experts_touched",
               "moe_expert_load_max", "moe_expert_load_mean")


def test_the_paged_step_counts_what_the_gather_step_counts(latent, tmp_path):
    """The same requests by both steps, serial (a ledger line then holds
    the counts of its own iteration's dispatches): every routed field of
    every ledger line is the same, so the benchmark's readers of them
    (``moe_decode_roofline_share``, ``moe_expert_load_max_over_mean``)
    read a paged run as they read a gathered one."""
    cfg, model, params = latent
    prompts = _prompts(8, (5, 23, 40, 17, 33, 9))
    runs = {}
    for kernel in (None, "pallas"):
        st, events = _counted_run(model, params, tmp_path / str(kernel),
                                  prompts, overlap="off", kernel=kernel)
        lines = [e for e in events if e.get("event") == "iteration_ledger"]
        runs[st.decode_path] = (st, [{k: e.get(k) for k in _MOE_FIELDS}
                                     for e in lines])
    (st_g, lines_g), (st_p, lines_p) = runs["gather"], runs["paged_kernel"]
    assert lines_g == lines_p
    assert any(line["moe_decode_pairs_held"] for line in lines_p)
    assert any(line["moe_experts_touched"] for line in lines_p)
    assert (st_g.moe_pairs, st_g.moe_pairs_held) \
        == (st_p.moe_pairs, st_p.moe_pairs_held)


def test_a_ledger_line_never_waits_for_the_step_in_flight(latent, tmp_path):
    """Dispatch-ahead with a sink: the counts a ledger line fetches are
    those a token fetch has passed, and the decode step dispatched in
    this iteration (committed in the next) keeps its entry until then,
    however many entries earlier lines have taken off the list."""
    cfg, model, params = latent
    obs.reset(out_dir=str(tmp_path / "telemetry"), enabled=True)
    try:
        eng = ServeEngine(model, params, num_slots=4, num_blocks=80,
                          overlap="on", **GEOM)
        resolve, kept = eng._moe_resolve, []

        def watched(everything=False):
            out = resolve(everything)
            if not everything and eng._pending is not None:
                # the newest entry is the pending step's own
                kept.append(bool(eng._moe_flight)
                            and eng._moe_flight[-1][2] is True)
            return out

        eng._moe_resolve = watched
        for p in _prompts(10, (21, 34, 9)):
            eng.submit(p, 12)
        eng.run()
        assert eng._moe_flight == []
        assert eng.stats().moe_pairs == (21 + 34 + 9 + 3 * 11) * 2 * 2
    finally:
        obs.reset(enabled=False)
    assert len(kept) >= 8 and all(kept)


def test_an_untraced_run_keeps_and_fetches_no_count(latent):
    cfg, model, params = latent
    assert not obs.has_sink()
    eng = ServeEngine(model, params, num_slots=4, num_blocks=80, **GEOM)
    for p in _prompts(9, (12, 30)):
        eng.submit(p, 6)
    eng.run()
    assert eng._moe_flight == [] and eng.stats().moe_pairs == 0


# -- how a prefill dispatch attended (ISSUE 32) --------------------------------

def _form_run(model, params, tmp_path):
    """Six requests through an engine under a sink: (engine, events)."""
    trace = [(p, 5) for p in _prompts(11, (5, 23, 40, 17, 33, 9))]
    obs.reset(out_dir=str(tmp_path / "telemetry"), enabled=True)
    try:
        eng = _serve_exact(model, params, trace, num_slots=4, num_blocks=80)
        obs.flush()
        return eng, _events(tmp_path)
    finally:
        obs.reset(enabled=False)


def _assert_every_dispatch_went(form, eng, events):
    other = (set(D.EXPANDED_FORMS) - {form}).pop()
    st = eng.stats()
    assert st.prefill_dispatches > 0
    assert st.prefill_dispatches_by_form == {form: st.prefill_dispatches,
                                             other: 0}
    assert eng.slo_summary()["prefill_dispatches_by_form"][form] \
        == st.prefill_dispatches
    spans = [e["args"] for e in events if e.get("type") == "span"
             and e["name"] == "serve/prefill_chunk"]
    assert len(spans) == st.prefill_dispatches
    assert all(a["latent_path"] == "expanded"
               and a["expanded_form"] == form for a in spans)
    # a decode step is absorbed: the form is an expanded call's
    assert all("expanded_form" not in e["args"] for e in events
               if e.get("type") == "span"
               and e["name"] == "serve/decode_step")
    report = [e for e in events if e.get("event") == "report"][-1]
    assert report["prefill_dispatches_by_form"] == {
        form: st.prefill_dispatches, other: 0}


def test_on_a_cpu_every_prefill_dispatch_is_the_xla_loop(lane_latent,
                                                         tmp_path):
    """Shapes the kernel has blocks for (chunks of 8 are whole multiples
    of nothing the chooser sees: ``KEY_BLOCK`` is 512), on a CPU: the
    XLA loop, written where ``decode_path`` is."""
    cfg, model, params = lane_latent
    eng, events = _form_run(model, params, tmp_path)
    _assert_every_dispatch_went("xla_loop", eng, events)


def test_seen_as_a_tpu_every_prefill_dispatch_is_the_kernel(
        lane_latent, seen_as_tpu, tmp_path, monkeypatch):
    """The forced TPU answer: the engine's chunked prefill attends
    through the fused kernel (interpret mode), row by row at its own
    ``start`` against both buckets, and stays ``generate_causal`` token
    for token (``_serve_exact``); span, stats, summary and report say
    ``kernel``."""
    from huggingface_sagemaker_tensorflow_distributed_tpu.ops import (
        pallas_latent_attention as K,
    )

    cfg, model, params = lane_latent
    traced = []
    kernel = K.latent_prefill_attention
    monkeypatch.setattr(
        K, "latent_prefill_attention", lambda *a, **kw: traced.append(
            (a[0].shape[0], a[2].shape[1])) or kernel(*a, **kw))
    eng, events = _form_run(model, params, tmp_path)
    _assert_every_dispatch_went("kernel", eng, events)
    # every prefill program traced the kernel, once a layer: [1, C] at
    # the first bucket, [4, C] at both (generate_causal's own prefills
    # of 8, 16, 24... tokens take it too)
    assert {(1, 16), (4, 16), (4, 64)} <= set(traced)


def test_a_k_v_engine_has_no_form(tmp_path):
    cfg, model, params = _llama()
    eng = ServeEngine(model, params, num_slots=4, num_blocks=30, **GEOM)
    eng.submit(_prompts(12, (9,))[0], 3)
    eng.run()
    assert eng.stats().prefill_dispatches_by_form is None
    assert "prefill_dispatches_by_form" not in eng.slo_summary()


@BOTH_PATHS
def test_no_compile_after_warmup(latent, tmp_path, kernel, path):
    """The rule of ``tests/test_serve_gates.py::test_no_compile_after_
    warmup`` for a latent engine: from empty jit caches, ``warmup()``
    compiles every program the run needs (three prefill programs, two
    decode buckets), whichever form the prefill ones attend by and
    whichever step decodes."""
    cfg, model, params = latent
    jax.clear_caches()
    obs.reset(out_dir=str(tmp_path / "telemetry"), enabled=True)
    try:
        tracker = obs.compile_tracker()
        eng = ServeEngine(model, params, num_slots=4, num_blocks=80,
                          kernel=kernel, **GEOM)
        assert eng.decode_path == path
        eng.warmup()
        count0 = tracker.count
        assert count0 > 0
        for p in _prompts(13, (5, 23, 40, 17, 33, 9)):
            eng.submit(p, 9)
        eng.run()
        st = eng.stats()
        assert st.bucket_switches > 0 and st.prefill_dispatches > 3
        assert tracker.count == count0, "compiled after warm-up"
    finally:
        obs.reset()
