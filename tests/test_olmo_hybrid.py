"""``models/olmo_hybrid.py`` (ISSUE 33): the model against the benchmark's
plain reference on seeded random weights, its cache path against its own
plain forward, what the loader refuses by name, and the three options of
``models/llama.py``'s block that its full layers run through, held to
leave every other configuration as it was (a golden taken on the parent
commit)."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import olmo_hybrid as reference
from huggingface_sagemaker_tensorflow_distributed_tpu.models import auto
from huggingface_sagemaker_tensorflow_distributed_tpu.models import (
    olmo_hybrid as O,
)
from huggingface_sagemaker_tensorflow_distributed_tpu.models.llama import (
    LlamaConfig,
    LlamaForCausalLM,
    llama_config_from_hf,
)

HF = {"model_type": "olmo_hybrid", "vocab_size": 96, "hidden_size": 32,
      "intermediate_size": 48, "num_hidden_layers": 4,
      "num_attention_heads": 4, "num_key_value_heads": 4,
      "layer_types": [O.LINEAR] * 3 + [O.FULL],
      "linear_num_key_heads": 2, "linear_num_value_heads": 2,
      "linear_key_head_dim": 8, "linear_value_head_dim": 16,
      "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
      "rope_parameters": {"rope_theta": None},
      "max_position_embeddings": 2048, "rms_norm_eps": 1e-6,
      "tie_word_embeddings": False, "attention_bias": False}


@pytest.fixture(scope="module")
def tiny():
    cfg = O.olmo_hybrid_config_from_hf(HF)
    model = O.OlmoHybridForCausalLM(cfg)
    params = model.init(jax.random.PRNGKey(3),
                        jnp.ones((1, 8), jnp.int32))["params"]
    return cfg, model, params


@pytest.mark.parametrize("length", [7, 64, 150, 1024])
def test_model_is_the_reference(tiny, length):
    """Float32 on both sides on the CPU: the chunked recurrence of the
    program against the reference's token-by-token scan, the Llama block's
    post-norm / query-key-norm / no-rotary options against the reference's
    own lines. Logits of order 0.1 agree to 1e-6 (measured 8e-7 at 150
    tokens); 2e-5 leaves room for 1,024 tokens of state and none for a
    wrong decay, beta, norm or convolution tap (each moves a logit by
    1e-2 or more)."""
    cfg, model, params = tiny
    tokens = jnp.asarray(np.random.default_rng(length).integers(
        3, cfg.vocab_size, size=length, dtype=np.int32))
    got = model.apply({"params": params}, tokens[None])[0]
    pad = -length % 512 if length > 512 else 0
    want = reference.logits(params, HF, jnp.pad(tokens, (0, pad)),
                            jnp.arange(length))
    assert float(jnp.abs(got).max()) > 0.1
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_reference_controls_move_the_logits(tiny):
    """The two readings a ``token_margin`` is set against exist: matmul
    operands through float8, and the state alone through bfloat16."""
    cfg, model, params = tiny
    tokens = jnp.asarray(np.random.default_rng(0).integers(
        3, cfg.vocab_size, size=96, dtype=np.int32))
    rows = jnp.arange(96)
    want = reference.logits(params, HF, tokens, rows)
    low = reference.logits(params, HF, tokens, rows, compute="float8_e4m3fn")
    held = reference.logits(params, HF, tokens, rows, state_dtype="bfloat16")
    assert float(jnp.abs(low - want).max()) > 0.05
    assert 1e-5 < float(jnp.abs(held - want).max()) < 0.05


def test_reference_reads_nothing_of_the_programs_models():
    src = open(reference.__file__).read()
    code = src.split('"""', 2)[2]
    assert "huggingface_sagemaker" not in src
    assert "models" not in code and "ops" not in code.replace("stops", "")
    with pytest.raises(ValueError, match="layer 0 is full_attention"):
        reference.logits(
            {"backbone": {"layers_0": {"linear_attn": {}},
                          "embed_tokens": {"embedding": jnp.ones((4, 4))}}},
            dict(HF, layer_types=[O.FULL], num_hidden_layers=1),
            jnp.zeros((2,), jnp.int32), jnp.arange(2))


def test_cache_path_is_the_plain_forward(tiny):
    """Prefill into a cache and single-token steps through it
    (``generate_causal``'s protocol: K/V rows for the full layer, state
    and convolution tail for the linear ones) against one plain forward."""
    cfg, model, params = tiny
    tokens = jnp.asarray(np.random.default_rng(1).integers(
        3, cfg.vocab_size, size=(2, 40), dtype=np.int32))
    want = model.apply({"params": params}, tokens)
    cache = model.apply({"params": params}, jnp.ones((2, 48), jnp.int32),
                        decode=True, mutable=["cache"])[1]["cache"]
    leaf = cache["backbone"]["layers_0"]["linear_attn"]
    assert leaf["recurrent_state"].shape == (2, 2, 8, 16)
    assert leaf["recurrent_state"].dtype == jnp.float32
    assert leaf["conv_state"].shape == (2, 3, 2 * (8 + 8 + 16))
    assert set(cache["backbone"]["layers_3"]["self_attn"]) == {
        "cached_key", "cached_value", "cache_index"}
    outs, at = [], 0
    for n in (33, 1, 1, 5):
        valid = (jnp.arange(48)[None] < at + n).astype(jnp.int32)
        lg, mut = model.apply(
            {"params": params, "cache": cache}, tokens[:, at:at + n],
            jnp.broadcast_to(valid, (2, 48)),
            position_ids=jnp.broadcast_to(at + jnp.arange(n)[None], (2, n)),
            decode=True, mutable=["cache"])
        cache, at = mut["cache"], at + n
        outs.append(lg)
    np.testing.assert_allclose(jnp.concatenate(outs, 1), want, atol=2e-5)


def test_token_mask_holds_state_and_tail_still(tiny):
    """A row whose tokens are all pads, and the pad tail of another: the
    cache comes back as it went in for the first, as the real tokens alone
    leave it for the second."""
    cfg, model, params = tiny
    tokens = jnp.asarray(np.random.default_rng(2).integers(
        3, cfg.vocab_size, size=(3, 16), dtype=np.int32))
    cache = model.apply({"params": params}, jnp.ones((3, 32), jnp.int32),
                        decode=True, mutable=["cache"])[1]["cache"]
    valid = jnp.broadcast_to((jnp.arange(32)[None] < 16).astype(jnp.int32),
                             (3, 32))
    pos = jnp.broadcast_to(jnp.arange(16)[None], (3, 16))

    def run(toks, mask):
        return model.apply({"params": params, "cache": cache}, toks, valid,
                           position_ids=pos, decode=True, mutable=["cache"],
                           token_mask=mask)[1]["cache"]

    n_real = jnp.array([16, 0, 9])
    mask = jnp.arange(16)[None] < n_real[:, None]
    out = run(tokens, mask)
    other = run(jnp.where(mask, tokens, 5), mask)
    for i in range(3):
        a = out["backbone"][f"layers_{i}"]["linear_attn"]
        b = other["backbone"][f"layers_{i}"]["linear_attn"]
        before = cache["backbone"][f"layers_{i}"]["linear_attn"]
        for name in ("recurrent_state", "conv_state"):
            np.testing.assert_array_equal(a[name][1], before[name][1])
            # layer 0 sees the tokens themselves; what a pad holds
            # reaches no layer's state
            np.testing.assert_array_equal(a[name], b[name])
            assert not np.array_equal(a[name][0], before[name][0])


@pytest.mark.parametrize("change, match", [
    ({"layer_types": [O.LINEAR, "sliding_attention", O.LINEAR, O.FULL]},
     "layer_types entry 'sliding_attention' is not implemented"),
    ({"linear_num_value_heads": 4},
     "linear_num_value_heads 4 != linear_num_key_heads 2"),
    ({"layer_types": [O.LINEAR] * 3}, "layer_types names 3 layers"),
    ({"layer_types": None}, "needs layer_types"),
    ({"rope_parameters": {"rope_theta": 500000.0}}, "rope_theta"),
    ({"attention_bias": True}, "attention_bias"),
])
def test_loader_refuses_by_name_what_it_does_not_run(change, match):
    with pytest.raises(ValueError, match=match):
        O.olmo_hybrid_config_from_hf(dict(HF, **change))


def test_auto_finds_the_family_by_model_type(tmp_path):
    with open(tmp_path / "config.json", "w") as f:
        json.dump(HF, f)
    assert auto.MODEL_REGISTRY[("olmo_hybrid", "causal-lm")] \
        is O.OlmoHybridForCausalLM
    assert auto.CONFIG_BUILDERS["olmo_hybrid"] \
        is O.olmo_hybrid_config_from_hf
    model, params, family = auto.from_pretrained(
        str(tmp_path), task="causal-lm", from_scratch=True)[:3]
    assert isinstance(model, O.OlmoHybridForCausalLM)
    assert model.config.layer_types == tuple(HF["layer_types"])


# -- models/llama.py: the three options leave everything else as it was ---------

GOLDEN = os.path.join(os.path.dirname(__file__), "data",
                      "qwen2_tiny_golden.json")


def test_qwen_tiny_model_is_the_parents():
    """Parameter names with shapes and logits (the plain forward, and a
    prefill into a cache followed by one decode step) of a tiny Qwen2
    model, against the golden the PARENT commit's ``models/llama.py``
    wrote (PR 33, before the options existed): bit for bit."""
    gold = json.load(open(GOLDEN))
    cfg = llama_config_from_hf(gold["hf_config"])
    assert (cfg.qk_norm, cfg.use_rope, cfg.post_norm) == (False, True, False)
    model = LlamaForCausalLM(cfg)
    tokens = jnp.asarray(gold["tokens"], jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens)["params"]
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    assert {"/".join(p.key for p in path): list(leaf.shape)
            for path, leaf in flat} == gold["param_shapes"]
    plain = model.apply({"params": params}, tokens)
    np.testing.assert_array_equal(
        np.asarray(plain, np.float64), np.asarray(gold["plain_logits"]))
    cache = model.apply({"params": params}, jnp.ones((1, 16), jnp.int32),
                        decode=True, mutable=["cache"])[1]["cache"]
    valid = (jnp.arange(16)[None] < 8).astype(jnp.int32)
    _, mut = model.apply({"params": params, "cache": cache}, tokens[:, :8],
                         valid, position_ids=jnp.arange(8)[None],
                         decode=True, mutable=["cache"])
    valid = (jnp.arange(16)[None] < 9).astype(jnp.int32)
    step, _ = model.apply({"params": params, "cache": mut["cache"]},
                          tokens[:, 8:9], valid,
                          position_ids=jnp.array([[8]]), decode=True,
                          mutable=["cache"])
    np.testing.assert_array_equal(
        np.asarray(step, np.float64), np.asarray(gold["decode_step_logits"]))


def test_the_options_change_what_they_name_and_nothing_else():
    base = dict(vocab_size=48, hidden_size=32, num_layers=1, num_heads=4,
                num_kv_heads=2, intermediate_size=64,
                max_position_embeddings=64)
    tokens = jnp.arange(10)[None]

    def names(**kw):
        model = LlamaForCausalLM(LlamaConfig(**base, **kw))
        params = model.init(jax.random.PRNGKey(0), tokens)["params"]
        return set(params["backbone"]["layers_0"]), set(
            params["backbone"]["layers_0"]["self_attn"]), model, params

    block, attn, model, params = names()
    assert block == {"input_ln", "self_attn", "post_attn_ln", "mlp"}
    assert attn == {"q_proj", "k_proj", "v_proj", "o_proj"}
    assert names(qk_norm=True)[1] == attn | {"q_norm", "k_norm"}
    assert names(post_norm=True)[0] == {"self_attn", "post_attn_ln",
                                        "post_mlp_ln", "mlp"}
    # without rotary embedding one causal layer reads its prefix as a set:
    # swapping two earlier tokens leaves a later position's logits as
    # they were; with it, it does not
    a, b = jnp.array([[5, 9, 7, 3]]), jnp.array([[9, 5, 7, 3]])
    no_rope = LlamaForCausalLM(LlamaConfig(**base, use_rope=False))
    np.testing.assert_allclose(no_rope.apply({"params": params}, a)[0, 2:],
                               no_rope.apply({"params": params}, b)[0, 2:],
                               atol=1e-6)
    assert float(jnp.abs(model.apply({"params": params}, a)[0, 2:]
                         - model.apply({"params": params}, b)[0, 2:]
                         ).max()) > 1e-4
    with pytest.raises(ValueError, match="post_norm"):
        LlamaConfig(**base, post_norm=True, pipeline_stages=2)


@pytest.mark.parametrize("hf, match", [
    ({"rope_scaling": {"type": "yarn", "factor": 4.0}}, "yarn"),
    ({"attention_bias": True}, "attention_bias"),
    ({"mlp_bias": True}, "mlp_bias"),
])
def test_llama_loader_still_refuses_what_it_refused(hf, match):
    base = {"model_type": "llama", "vocab_size": 48, "hidden_size": 32,
            "num_hidden_layers": 1, "num_attention_heads": 4,
            "intermediate_size": 64}
    with pytest.raises(ValueError, match=match):
        llama_config_from_hf(dict(base, **hf))
