"""A model whose residual path is hyper-connections under a sigmoid,
bias-corrected gate (``models/xing4.py``, ISSUE 35) through the serving
engine: requests of different lengths, prompts longer than a chunk,
four-row prefill dispatches and decode through the latent pool against
the benchmark's plain reference (both decode paths; four streams, as
published: ``test_xing4.py`` holds two and four to the reference); a
prompt sent twice hits the prefix cache and yields the same tokens (the
latent pool carries everything a request needs); the spans and counters;
the engine needs no knowledge of the streams, and a model without the
wrap keeps its telemetry to the key."""

import json

import jax.numpy as jnp
import numpy as np
import pytest
from test_xing4 import build, tiny_deepseek_v2

from chipbench.reference import xing4 as reference
from huggingface_sagemaker_tensorflow_distributed_tpu import obs
from huggingface_sagemaker_tensorflow_distributed_tpu.models import (
    deepseek_v2 as D,
)
from huggingface_sagemaker_tensorflow_distributed_tpu.serve import engine as E
from huggingface_sagemaker_tensorflow_distributed_tpu.serve.engine import (
    ServeEngine,
)

GEOM = dict(block_size=8, prefill_chunk=16, max_model_len=128)
NEW_KEYS = {"residual_streams", "gate", "mhc_defect_max"}


@pytest.fixture(scope="module", params=[4], ids=["n4"])
def served(request):
    return build(request.param, seed=10 + request.param)


def _prompts(seed, lengths):
    rng = np.random.RandomState(seed)
    return [rng.randint(3, 90, (n,)).astype(np.int32) for n in lengths]


def _gap(hf, params, prompt, out) -> float:
    """How far under the reference's maximum the engine's tokens lie,
    teacher-forced over prompt + output (``kinds/serve.py::_check``)."""
    seq = np.concatenate([prompt, out]).astype(np.int32)
    lg = np.asarray(reference.logits(
        params, hf, jnp.asarray(seq),
        jnp.arange(len(prompt) - 1, len(seq) - 1)))
    return float((lg.max(-1) - lg[np.arange(len(out)), out]).max())


def _serve(model, params, prompts, max_new, **kw):
    eng = ServeEngine(model, params, **{**GEOM, **kw})
    reqs = [eng.submit(p, max_new) for p in prompts]
    eng.run()
    return eng, reqs


def _events(tmp_path):
    return [json.loads(line)
            for f in sorted((tmp_path / "telemetry").glob("*.jsonl"))
            for line in open(f)]


@pytest.mark.parametrize("kernel, overlap", [(None, "on"), (None, "off"),
                                             ("pallas", "on")])
def test_engine_agrees_with_the_reference(served, kernel, overlap):
    """Six requests on four slots (two wait for a slot another leaves),
    prompts from 5 to 50 tokens against a chunk of 16, the opening in
    four-row dispatches, decode through the latent pool: every token the
    engine chose lies within 1e-5 of the reference's maximum (float32 on
    both sides; an unrelated token lies 0.4 under)."""
    hf, model, params = served
    prompts = _prompts(1, (5, 23, 50, 17, 33, 16))
    eng, reqs = _serve(model, params, prompts, 9, num_slots=4,
                       num_blocks=60, kernel=kernel, overlap=overlap)
    st = eng.stats()
    assert st.decode_path == ("paged_kernel" if kernel else "gather")
    assert st.preemptions == 0 and st.decode_steps > 0
    assert st.prefill_dispatches < st.prefill_chunks   # rows were batched
    for p, r in zip(prompts, reqs):
        out = eng.output_ids(r)
        assert len(out) == 9
        assert _gap(hf, params, p, out) <= 1e-5, (len(p), out)
    # the plan knows latent rows and nothing of the streams: one pool a
    # layer, rows with no heads axis, no state beside them
    kinds = [k[0] for k in eng._plan.kinds]
    assert kinds.count("latent") == 3 and "state" not in kinds
    assert all(p.shape == (60, 8, 128) for p in eng._pools)
    assert st.latent_bytes_per_token == 3 * 128 * 4
    assert (st.residual_streams, st.gate) == (hf["hc_mult"], "sigmoid_bias")
    assert st.prefix_cache is True


def test_the_same_prompt_twice_hits_the_prefix_cache(served):
    hf, model, params = served
    (p,) = _prompts(4, (41,))
    eng = ServeEngine(model, params, num_slots=2, num_blocks=60, **GEOM)
    a = eng.submit(p, 6)
    eng.run()
    b = eng.submit(p, 6)
    eng.run()
    assert a.prefix_cached_tokens == 0 and b.prefix_cached_tokens >= 32
    np.testing.assert_array_equal(eng.output_ids(a), eng.output_ids(b))
    assert _gap(hf, params, p, eng.output_ids(b)) <= 1e-5
    st = eng.stats()
    assert st.prefix_cached_tokens > 0 and st.cache_hit_rate > 0


def test_preemption_recomputes_and_stays_on_the_reference(served):
    hf, model, params = served
    prompts = _prompts(3, (30, 28, 26, 31))
    eng, reqs = _serve(model, params, prompts, 30, num_slots=4,
                       num_blocks=22)
    assert eng.stats().preemptions > 0
    for p, r in zip(prompts, reqs):
        assert _gap(hf, params, p, eng.output_ids(r)) <= 1e-5


@pytest.mark.parametrize("overlap", ["on", "off"])
def test_spans_and_ledger_carry_the_streams_the_gate_and_the_defect(
        served, tmp_path, overlap):
    hf, model, params = served
    obs.reset(out_dir=str(tmp_path / "telemetry"), enabled=True)
    try:
        prompts = _prompts(6, (5, 23, 40, 17, 33, 9))
        eng, reqs = _serve(model, params, prompts, 7, num_slots=4,
                           num_blocks=60, overlap=overlap)
        st = eng.stats()
        obs.flush()
        events = _events(tmp_path)
    finally:
        obs.reset(enabled=False)
    spans = [e for e in events if e.get("type") == "span"]
    steps = [e["args"] for e in spans
             if e["name"] in ("serve/prefill_chunk", "serve/decode_step")]
    assert steps and all(
        (a["residual_streams"], a["gate"]) == (hf["hc_mult"], "sigmoid_bias")
        for a in steps)
    assert {a["latent_path"] for a in steps} == {"absorbed", "expanded"}
    lines = [e for e in events if e.get("event") == "iteration_ledger"]
    # the routed counts under the names they have, and the defect beside
    # them on every line that landed a dispatch
    assert lines and all("moe_pairs" in e for e in lines)
    tokens = sum(len(p) for p in prompts) + st.decode_tokens
    assert st.moe_pairs == tokens * 2 * 2
    assert st.moe_pairs_held == st.moe_pairs       # every expert is held
    landed = [e for e in lines if e["moe_pairs"]]
    assert landed and all(0 <= e["mhc_defect_max"] < 0.5 for e in landed)
    assert any(e["mhc_defect_max"] > 0 for e in landed)
    for e in lines:
        if e.get("moe_experts_touched") is not None:
            assert len(e["moe_experts_touched"]) == 2
            assert all(0 < n <= 8 for n in e["moe_experts_touched"])
    report = [e for e in events if e.get("event") == "report"][-1]
    assert (report["residual_streams"], report["gate"]) == (
        hf["hc_mult"], "sigmoid_bias")
    assert report["latent_bytes_per_token"] == st.latent_bytes_per_token
    summary = eng.slo_summary()
    assert (summary["residual_streams"], summary["gate"]) == (
        hf["hc_mult"], "sigmoid_bias")
    from huggingface_sagemaker_tensorflow_distributed_tpu.obs import schema
    assert [p for e in events for p in schema.validate_event(e)] == []


def test_an_untraced_run_fetches_no_count(served):
    hf, model, params = served
    eng, _ = _serve(model, params, _prompts(7, (9, 21)), 5, num_slots=2,
                    num_blocks=40)
    assert eng._moe_flight == [] and eng.stats().moe_pairs == 0


def test_the_steps_return_the_defect_behind_the_counts(served):
    """``_moe_counts`` of a model with the wrap: the counts ``[expert
    layers, held]`` and ONE float; of DeepSeek-V2, the counts alone."""
    hf, model, params = served
    _, mut = model.apply({"params": params}, jnp.ones((2, 8), jnp.int32),
                         mutable=[D.MOE_STATS])
    counts, defect = E._moe_counts(mut)
    assert counts.shape == (2, 8) and defect.shape == ()
    assert int(counts.sum()) == 2 * 8 * 2 * 2


# -- a model without the wrap is left as it was -----------------------------------

def test_a_model_without_the_wrap_has_none_of_the_new_keys(tmp_path):
    model, params = tiny_deepseek_v2()
    _, mut = model.apply({"params": params}, jnp.ones((2, 8), jnp.int32),
                         mutable=[D.MOE_STATS])
    assert len(E._moe_counts(mut)) == 1
    obs.reset(out_dir=str(tmp_path / "telemetry"), enabled=True)
    try:
        eng, _ = _serve(model, params, _prompts(8, (9, 21, 30)), 6,
                        num_slots=2, num_blocks=40)
        st = eng.stats()
        summary = eng.slo_summary()
        obs.flush()
        events = _events(tmp_path)
    finally:
        obs.reset(enabled=False)
    assert st.residual_streams is None and st.gate is None
    assert not NEW_KEYS & set(summary)
    for e in events:
        assert not NEW_KEYS & set(e), e
        assert not NEW_KEYS & set(e.get("args") or {}), e
    assert all(len(f) == 3 for f in eng._moe_flight)
