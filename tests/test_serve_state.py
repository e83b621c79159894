"""A model with recurrent (linear-attention) layers through the serving
engine (ISSUE 33): per-slot state pools beside the paged K/V of its full
layers. Requests of different lengths, prompts longer than a chunk,
four-row prefill dispatches and decode through the cache against the
benchmark's plain reference (both decode paths); slot reuse; what stands
down and what raises by name; the spans and counters; a K/V model's plan,
programs and telemetry left as they were."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import olmo_hybrid as reference
from huggingface_sagemaker_tensorflow_distributed_tpu import obs
from huggingface_sagemaker_tensorflow_distributed_tpu.models import (
    olmo_hybrid as O,
)
from huggingface_sagemaker_tensorflow_distributed_tpu.models.llama import (
    LlamaConfig,
    LlamaForCausalLM,
)
from huggingface_sagemaker_tensorflow_distributed_tpu.serve import engine as E
from huggingface_sagemaker_tensorflow_distributed_tpu.serve import transport
from huggingface_sagemaker_tensorflow_distributed_tpu.serve.engine import (
    ServeEngine,
)

HF = {"model_type": "olmo_hybrid", "vocab_size": 96, "hidden_size": 32,
      "intermediate_size": 48, "num_hidden_layers": 4,
      "num_attention_heads": 2, "num_key_value_heads": 2,
      "layer_types": [O.LINEAR] * 3 + [O.FULL],
      "linear_num_key_heads": 2, "linear_num_value_heads": 2,
      "linear_key_head_dim": 8, "linear_value_head_dim": 16,
      "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
      "rope_parameters": {"rope_theta": None},
      "max_position_embeddings": 512, "rms_norm_eps": 1e-6,
      "tie_word_embeddings": False}
GEOM = dict(block_size=8, prefill_chunk=16, max_model_len=128)
STATE_BYTES = 3 * (2 * 8 * 16 * 4 + 3 * 2 * (8 + 8 + 16) * 4)


@pytest.fixture(scope="module")
def hybrid():
    # eos outside the vocabulary: no request ends early
    cfg = O.olmo_hybrid_config_from_hf(HF, eos_token_id=100257,
                                       pad_token_id=0)
    model = O.OlmoHybridForCausalLM(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.ones((1, 8), jnp.int32))["params"]
    return cfg, model, params


def _prompts(seed, lengths):
    rng = np.random.RandomState(seed)
    return [rng.randint(3, 90, (n,)).astype(np.int32) for n in lengths]


def _gap(params, prompt, out) -> float:
    """How far under the reference's maximum the engine's tokens lie,
    teacher-forced over prompt + output (``kinds/serve.py::_check``)."""
    seq = np.concatenate([prompt, out]).astype(np.int32)
    lg = np.asarray(reference.logits(
        params, HF, jnp.asarray(seq),
        jnp.arange(len(prompt) - 1, len(seq) - 1)))
    return float((lg.max(-1) - lg[np.arange(len(out)), out]).max())


def _serve(model, params, prompts, max_new, **kw):
    eng = ServeEngine(model, params, **{**GEOM, **kw})
    reqs = [eng.submit(p, max_new) for p in prompts]
    eng.run()
    return eng, reqs


@pytest.mark.parametrize("kernel, overlap", [("xla", "on"), ("xla", "off"),
                                             ("pallas", "on")])
def test_engine_agrees_with_the_reference(hybrid, kernel, overlap):
    """Six requests on four slots (two wait for a slot another leaves),
    prompts from 5 to 50 tokens against a chunk of 16, the opening in
    four-row dispatches, decode through state pools and paged K/V: every
    token the engine chose is the reference's own maximum (float32 on
    both sides: the gap reads 0; an unrelated token lies 0.3 under)."""
    cfg, model, params = hybrid
    prompts = _prompts(1, (5, 23, 50, 17, 33, 16))
    eng, reqs = _serve(model, params, prompts, 9, num_slots=4,
                       num_blocks=60, kernel=kernel, overlap=overlap)
    st = eng.stats()
    assert st.decode_path == ("paged_kernel" if kernel == "pallas"
                              else "gather")
    assert st.preemptions == 0 and st.decode_steps > 0
    assert st.prefill_dispatches < st.prefill_chunks   # rows were batched
    for p, r in zip(prompts, reqs):
        out = eng.output_ids(r)
        assert len(out) == 9
        assert _gap(params, p, out) <= 1e-5, (len(p), out)
    assert [k[0] for k in eng._plan.kinds].count("state") == 6
    assert st.state_bytes_per_slot == STATE_BYTES
    assert st.state_pool_bytes == 4 * STATE_BYTES      # a row a slot
    assert st.state_slots_peak == 4


def test_a_left_slot_gives_its_next_request_a_fresh_engines_logits(hybrid):
    """One slot, two requests one after the other: the second starts from
    zeros (``start == 0`` in the program, no clearing pass), whatever the
    first left in the slot's state rows."""
    cfg, model, params = hybrid
    first, second = _prompts(2, (37, 21))
    eng, (r1, r2) = _serve(model, params, [first, second], 12, num_slots=1,
                           num_blocks=40)
    assert float(jnp.abs(eng._states[1][0]).max()) > 0   # the slot was used
    fresh, (only,) = _serve(model, params, [second], 12, num_slots=1,
                            num_blocks=40)
    np.testing.assert_array_equal(eng.output_ids(r2), fresh.output_ids(only))
    assert _gap(params, second, eng.output_ids(r2)) <= 1e-5
    for a, b in zip(eng._states, fresh._states):
        assert a.shape[0] == 1                     # a row a slot, no more
        np.testing.assert_allclose(a[0], b[0], atol=1e-6)


def test_preemption_recomputes_from_zero(hybrid):
    """A pool too small for every running request: victims are preempted
    and re-admitted at 0 (their output folded into the prompt), and still
    end on the reference's tokens."""
    cfg, model, params = hybrid
    prompts = _prompts(3, (30, 28, 26, 31))
    eng, reqs = _serve(model, params, prompts, 30, num_slots=4,
                       num_blocks=22)
    assert eng.stats().preemptions > 0
    for p, r in zip(prompts, reqs):
        assert _gap(params, p, eng.output_ids(r)) <= 1e-5


def test_the_same_prompt_twice_hits_no_prefix(hybrid):
    cfg, model, params = hybrid
    (p,) = _prompts(4, (41,))
    eng = ServeEngine(model, params, num_slots=2, num_blocks=60, **GEOM)
    a = eng.submit(p, 6)
    eng.run()
    b = eng.submit(p, 6)
    eng.run()
    assert a.prefix_cached_tokens == b.prefix_cached_tokens == 0
    assert a.prefix_prompt_tokens == b.prefix_prompt_tokens == 41
    np.testing.assert_array_equal(eng.output_ids(a), eng.output_ids(b))
    assert eng.blocks.num_cached == 0          # nothing was parked either
    st = eng.stats()
    assert st.prefix_cache == "off (recurrent state)"
    assert st.prefix_cached_tokens == 0 and st.cow_copies == 0
    summary = eng.slo_summary()
    assert summary["prefix_cache"] == "off (recurrent state)"
    assert summary["state_bytes_per_slot"] == STATE_BYTES
    assert summary["state_pool_bytes"] == 2 * STATE_BYTES
    assert summary["kv_token_bytes"] == st.kv_token_bytes == 2 * 2 * 16 * 4


def test_swap_migration_and_speculation_raise_by_name(hybrid):
    cfg, model, params = hybrid
    with pytest.raises(ValueError, match="swap='always'.*recurrent state"):
        ServeEngine(model, params, swap="always", **GEOM)
    with pytest.raises(ValueError, match="speculate_k.*recurrent state"):
        ServeEngine(model, params, speculate_k=2, draft=1, **GEOM)
    with pytest.raises(ValueError, match="recurrent state.*mesh"):
        ServeEngine(model, params, mesh=2, **GEOM)
    src = ServeEngine(model, params, num_slots=2, num_blocks=40, **GEOM)
    dst = ServeEngine(model, params, num_slots=2, num_blocks=40, **GEOM)
    req = src.submit(_prompts(5, (20,))[0], 8)
    src.step()
    with pytest.raises(transport.TransportError,
                       match="migrate_request.*recurrent state"):
        transport.migrate_request(src, dst, req.rid)
    src.run()                                  # and the request is unharmed
    assert len(src.output_ids(req)) == 8


def _events(tmp_path):
    with open(tmp_path / "telemetry" / "events.jsonl") as f:
        return [json.loads(line) for line in f]


@pytest.mark.parametrize("overlap", ["on", "off"])
def test_spans_and_ledger_carry_the_state_counts(hybrid, tmp_path, overlap):
    cfg, model, params = hybrid
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
    forms = {(e["name"], e["args"]["state_form"]) for e in spans
             if e["name"] in ("serve/prefill_chunk", "serve/decode_step")}
    assert forms == {("serve/prefill_chunk", "chunked"),
                     ("serve/decode_step", "step")}
    pre = [e["args"] for e in spans if e["name"] == "serve/prefill_chunk"]
    assert all(a["state_rows"] == a["chunks"] <= a["rows"] for a in pre)
    dec = [e["args"] for e in spans if e["name"] == "serve/decode_step"]
    assert all(a["state_rows"] == a["active"] for a in dec)
    lines = [e for e in events if e.get("event") == "iteration_ledger"]
    assert lines and all("state_slots" in e for e in lines)
    # every row of every dispatch, once: real prefill rows + decode slots
    assert sum(e["state_slots"] for e in lines) == (
        st.prefill_chunks + sum(a["active"] for a in dec))
    assert sum(e["prefill_tokens"] for e in lines) == sum(
        len(p) for p in prompts)
    for e in lines:
        slots = e["state_slots"] - e["prefill_chunks"]
        assert (e["kv_tokens_resident"] > 0) == (slots > 0)
        assert e["kv_tokens_resident"] <= slots * GEOM["max_model_len"]
        assert 0 < e["state_slots_peak"] <= 4
    assert lines[-1]["state_slots_peak"] == st.state_slots_peak == 4
    report = [e for e in events if e.get("event") == "report"][-1]
    assert report["prefix_cache"] == "off (recurrent state)"
    assert report["state_bytes_per_slot"] == STATE_BYTES
    assert report["state_slots_peak"] == 4
    from huggingface_sagemaker_tensorflow_distributed_tpu.obs import schema
    assert [p for e in events for p in schema.validate_event(e)] == []


@pytest.mark.parametrize("seen", ["xla", "kernel"])
def test_state_step_is_on_the_decode_spans_and_in_the_stats(
        tmp_path, monkeypatch, seen):
    """Sixteen heads of ``8 x 16``: eight side by side fill a lane tile,
    so the state is carried ``[2, 8, 128]`` and the fused kernel has
    blocks for it. On this CPU the one-token step is the jnp form
    (``xla``); steered as a TPU would see it (in the test: the program has
    no option for it) the decode steps run the kernel, interpreted, and
    end on the reference's tokens all the same. The jitted steps are keyed
    on the model, not on what it sees, so a case starts and ends with
    empty caches."""
    from huggingface_sagemaker_tensorflow_distributed_tpu.ops import (
        pallas_gated_delta,
    )

    hf = dict(HF, linear_num_key_heads=16, linear_num_value_heads=16)
    cfg = O.olmo_hybrid_config_from_hf(hf, eos_token_id=100257,
                                       pad_token_id=0)
    jax.clear_caches()
    if seen == "kernel":
        monkeypatch.setattr(
            O, "state_step", lambda cfg: pallas_gated_delta.state_step(
                cfg.linear_num_heads, cfg.linear_key_head_dim,
                cfg.linear_value_head_dim, platform="tpu"))
    model = O.OlmoHybridForCausalLM(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.ones((1, 8), jnp.int32))["params"]
    obs.reset(out_dir=str(tmp_path / "telemetry"), enabled=True)
    try:
        prompts = _prompts(8, (5, 23, 17))
        eng, reqs = _serve(model, params, prompts, 6, num_slots=2,
                           num_blocks=40)
        st = eng.stats()
        obs.flush()
        events = _events(tmp_path)
    finally:
        obs.reset(enabled=False)
        jax.clear_caches()
    assert ((2, 8, 128), "float32") in eng._plan.state_shapes
    assert st.state_step == eng.slo_summary()["state_step"] == seen
    for p, r in zip(prompts, reqs):
        lg = np.asarray(reference.logits(
            params, hf, jnp.asarray(np.concatenate([p, eng.output_ids(r)])),
            jnp.arange(len(p) - 1, len(p) + 5)))
        out = eng.output_ids(r)
        assert float((lg.max(-1) - lg[np.arange(6), out]).max()) <= 1e-5
    spans = [e for e in events if e.get("type") == "span"]
    dec = [e["args"] for e in spans if e["name"] == "serve/decode_step"]
    assert dec and all(a["state_step"] == seen for a in dec)
    assert all("state_step" not in e["args"] for e in spans
               if e["name"] == "serve/prefill_chunk")
    report = [e for e in events if e.get("event") == "report"][-1]
    assert report["state_step"] == seen
    from huggingface_sagemaker_tensorflow_distributed_tpu.obs import schema
    assert [p for e in events for p in schema.validate_event(e)] == []


@pytest.mark.parametrize("seen", ["xla", "kernel"])
def test_state_chunk_is_on_the_prefill_spans_and_in_the_stats(
        tmp_path, monkeypatch, seen):
    """Four heads of ``8 x 64``: two side by side fill a lane tile, so the
    state is carried ``[2, 8, 128]`` and the fused chunk kernel has blocks
    for it. On this CPU a prefill dispatch runs the XLA form (``xla``);
    steered as a TPU would see it (in the test: the program has no option
    for it) the dispatches run the kernel, interpreted, and the engine
    serves the reference's tokens all the same. Every prefill span, the
    stats, the summary and the report say which; the schema types it, and
    the telemetry linter passes the run's events."""
    from huggingface_sagemaker_tensorflow_distributed_tpu.ops import (
        pallas_gated_delta,
    )
    from huggingface_sagemaker_tensorflow_distributed_tpu.obs import schema

    hf = dict(HF, linear_num_key_heads=4, linear_num_value_heads=4,
              linear_value_head_dim=64)
    cfg = O.olmo_hybrid_config_from_hf(hf, eos_token_id=100257,
                                       pad_token_id=0)
    jax.clear_caches()
    if seen == "kernel":
        monkeypatch.setattr(
            O, "state_chunk", lambda cfg: pallas_gated_delta.state_chunk(
                cfg.linear_num_heads, cfg.linear_key_head_dim,
                cfg.linear_value_head_dim, platform="tpu"))
    model = O.OlmoHybridForCausalLM(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.ones((1, 8), jnp.int32))["params"]
    obs.reset(out_dir=str(tmp_path / "telemetry"), enabled=True)
    try:
        prompts = _prompts(9, (5, 23, 40))
        eng, reqs = _serve(model, params, prompts, 6, num_slots=2,
                           num_blocks=40)
        st = eng.stats()
        obs.flush()
        events = _events(tmp_path)
    finally:
        obs.reset(enabled=False)
        jax.clear_caches()
    assert ((2, 8, 128), "float32") in eng._plan.state_shapes
    assert st.state_chunk == eng.slo_summary()["state_chunk"] == seen
    assert st.state_step == "xla"
    for p, r in zip(prompts, reqs):
        out = eng.output_ids(r)
        lg = np.asarray(reference.logits(
            params, hf, jnp.asarray(np.concatenate([p, out])),
            jnp.arange(len(p) - 1, len(p) + 5)))
        assert float((lg.max(-1) - lg[np.arange(6), out]).max()) <= 1e-5
    spans = [e for e in events if e.get("type") == "span"]
    pre = [e["args"] for e in spans if e["name"] == "serve/prefill_chunk"]
    assert pre and all(a["state_form"] == "chunked"
                       and a["state_chunk"] == seen for a in pre)
    assert all("state_chunk" not in e["args"] for e in spans
               if e["name"] == "serve/decode_step")
    report = [e for e in events if e.get("event") == "report"][-1]
    assert report["state_chunk"] == seen
    assert [p for e in events for p in schema.validate_event(e)] == []
    assert schema.validate_event(dict(report, state_chunk=3))
    lint = subprocess.run(
        [sys.executable, os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "scripts",
            "check_telemetry_schema.py"),
         str(tmp_path / "telemetry" / "events.jsonl")],
        capture_output=True, text=True)
    assert lint.returncode == 0, lint.stdout + lint.stderr


# -- a K/V model is left as it was ----------------------------------------------

def _llama():
    cfg = LlamaConfig(vocab_size=96, hidden_size=32, num_layers=2,
                      num_heads=2, num_kv_heads=2, intermediate_size=48,
                      max_position_embeddings=128, eos_token_id=95,
                      pad_token_id=0)
    model = LlamaForCausalLM(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.ones((1, 8), jnp.int32))["params"]
    return cfg, model, params


def test_a_k_v_model_has_no_state_operand_and_no_state_field(tmp_path):
    """Its plan has no ``state`` kind, its steps take and return what they
    always did (no state pool among a step's operands or results), and
    its ledger, spans, summary and report name none of the new fields."""
    cfg, model, params = _llama()
    obs.reset(out_dir=str(tmp_path / "telemetry"), enabled=True)
    try:
        eng, _ = _serve(model, params, _prompts(7, (9, 30)), 5, num_slots=2,
                        num_blocks=40)
        obs.flush()
        events = _events(tmp_path)
    finally:
        obs.reset(enabled=False)
    assert eng._plan.state_shapes == () and eng._states == []
    assert eng._state_args() == () and eng._state_args(np.zeros(4)) == ()
    st = eng.stats()
    assert st.prefix_cache is True and st.state_bytes_per_slot is None
    assert st.state_pool_bytes is None and st.state_slots_peak is None
    new = {"state_slots", "kv_tokens_resident", "state_slots_peak",
           "prefill_tokens", "state_bytes_per_slot", "state_pool_bytes",
           "state_form", "state_rows", "state_step", "state_chunk"}
    for e in events:
        assert not new & (set(e) | set(e.get("args") or {})), e
    assert "kv_token_bytes" not in eng.slo_summary()
    # the step programs: as many operands as the call without states has
    S, nb = 2, eng.max_blocks_per_seq
    zi, zf = np.zeros((S,), np.int32), np.zeros((S,), np.float32)
    args = (params, eng._pools, zi, np.zeros((S, nb), np.int32), zi,
            np.zeros((S,), bool), zf, zi, zf, np.zeros((S, 2), np.uint32),
            zi)
    jaxpr = jax.make_jaxpr(
        lambda *a: E._decode_step(model, *a, eng._plan, 128, False))(*args)
    n_in = len(jax.tree_util.tree_leaves(args))
    assert len(jaxpr.jaxpr.invars) == n_in
    assert len(jaxpr.jaxpr.outvars) == 1 + len(eng._pools)


def test_a_model_with_state_alone_is_refused(hybrid):
    cfg = O.olmo_hybrid_config_from_hf(
        dict(HF, layer_types=[O.LINEAR] * 4), eos_token_id=100257,
        pad_token_id=0)
    model = O.OlmoHybridForCausalLM(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.ones((1, 8), jnp.int32))["params"]
    with pytest.raises(ValueError, match="no paged K/V"):
        ServeEngine(model, params, **GEOM)
