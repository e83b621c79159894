"""Xing4 (``models/xing4.py``, ISSUE 35): the hyper-connection wrap and the
sigmoid, bias-corrected, renormalised gate against ``numpy``
transcriptions of their equations, the model against the benchmark's
plain reference, what the loader refuses, and DeepSeek-V2's gate left as
it was. Every parameter of the wrap (``phi``, ``alpha``, the three ``b``)
and the gate's bias are drawn at order 1, so that the dynamic part of the
maps and the bias matter; two and four residual streams."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import xing4 as reference
from huggingface_sagemaker_tensorflow_distributed_tpu.models import (
    deepseek_v2 as D,
    moe,
    xing4 as X,
)
from huggingface_sagemaker_tensorflow_distributed_tpu.models.auto import (
    CONFIG_BUILDERS,
    MODEL_REGISTRY,
    detect_family,
)

STREAMS = pytest.mark.parametrize("n", [2, 4], ids=["n2", "n4"])
ITERS, EPS, CLAMP = 20, 1e-6, (-30.0, 30.0)


def hf_config(n: int = 4, **over) -> dict:
    """A tiny ``config.json`` mapping of the family (also the reference's
    configuration FILE: every expert held)."""
    return {
        "model_type": "xing4_0", "vocab_size": 96, "hidden_size": 32,
        "num_hidden_layers": 3, "first_k_dense_replace": 1,
        "num_attention_heads": 2, "q_lora_rank": 12, "kv_lora_rank": 8,
        "qk_nope_head_dim": 8, "qk_rope_head_dim": 4, "v_head_dim": 8,
        "intermediate_size": 48, "moe_intermediate_size": 16,
        "n_routed_experts": 8, "n_shared_experts": 1,
        "num_experts_per_tok": 2, "n_group": 1, "topk_group": 1,
        "scoring_func": "sigmoid", "topk_method": "noaux_tc",
        "norm_topk_prob": True, "routed_scaling_factor": 2,
        "max_position_embeddings": 512, "rope_theta": 10000,
        "rms_norm_eps": 1e-6, "rope_scaling": {
            "type": "yarn", "factor": 64, "mscale": 1, "mscale_all_dim": 1,
            "beta_fast": 32, "beta_slow": 1,
            "original_max_position_embeddings": 64},
        "hc_mult": n, "hc_sinkhorn_iters": ITERS, "hc_eps": EPS,
        "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30,
        "expert_parallel": 1, "expert_rank": 0, **over}


def order_one(params, seed: int):
    """``params`` with the wrap's parameters and the gate's bias redrawn
    at order 1: ``phi`` so that its 24 outputs have unit variance, the
    rest standard normal."""
    rng = np.random.default_rng(seed)

    def draw(path, leaf):
        name = str(getattr(path[-1], "key", path[-1]))
        if name == "phi":
            return jnp.asarray(rng.normal(size=leaf.shape)
                               / np.sqrt(leaf.shape[0]), leaf.dtype)
        if name in ("alpha", "b_pre", "b_post", "b_res",
                    "e_score_correction_bias"):
            return jnp.asarray(rng.normal(size=leaf.shape), leaf.dtype)
        return leaf

    return jax.tree_util.tree_map_with_path(draw, params)


def build(n: int = 4, seed: int = 0, **over):
    hf = hf_config(n, **over)
    # eos outside the vocabulary: no request ends early
    cfg = X.xing4_config_from_hf(hf, eos_token_id=100001, pad_token_id=0)
    model = X.Xing4ForCausalLM(cfg)
    params = model.init(jax.random.PRNGKey(seed),
                        jnp.ones((1, 8), jnp.int32))["params"]
    return hf, model, order_one(params, seed + 1)


# -- (a) the wrap -----------------------------------------------------------------

def np_wrap(x, y, phi, alpha, b_pre, b_post, b_res, iters=ITERS,
            rows_first=False, clamp=CLAMP):
    """The equations of ISSUE 35, transcribed: ``x`` [T, n, C] streams,
    ``y`` [T, C] the sub-layer's output. Returns ``(u, x+, H_res)`` in
    float64."""
    x, y = x.astype(np.float64), y.astype(np.float64)
    t, n, _ = x.shape
    flat = x.reshape(t, -1)
    xbar = flat / np.sqrt((flat ** 2).mean(-1, keepdims=True) + EPS)
    h = xbar @ phi.astype(np.float64)
    h_pre = 1 / (1 + np.exp(-(alpha[0] * h[:, :n] + b_pre)))
    h_post = 2 / (1 + np.exp(-(alpha[1] * h[:, n:2 * n] + b_post)))
    raw = alpha[2] * h[:, 2 * n:].reshape(t, n, n) + b_res
    m = np.exp(raw if clamp is None else np.clip(raw, *clamp))
    for _ in range(iters):
        for axis in ((2, 1) if rows_first else (1, 2)):   # 1: column sums
            m = m / (m.sum(axis=axis, keepdims=True) + EPS)
    u = np.einsum("tn,tnc->tc", h_pre, x)
    return u, np.einsum("tmn,tnc->tmc", m, x) + h_post[:, :, None] * y[
        :, None, :], m


def _wrap_inputs(n, seed=0, scale_res=0.5):
    rng = np.random.default_rng(seed)
    t, c = 24, 16
    x = rng.normal(size=(t, n, c)).astype(np.float32)
    y = rng.normal(size=(t, c)).astype(np.float32)
    phi = (rng.normal(size=(n * c, 2 * n + n * n)) / np.sqrt(n * c)).astype(
        np.float32)
    alpha = np.array([0.9, -1.1, scale_res], np.float32)
    b = [rng.normal(size=s).astype(np.float32) for s in ((n,), (n,), (n, n))]
    return x, y, phi, alpha, b


def _program_wrap(x, y, phi, alpha, b):
    h_pre, h_post, h_res = X.mhc_maps(
        jnp.asarray(x), jnp.asarray(phi), jnp.asarray(alpha),
        *(jnp.asarray(v) for v in b), iters=ITERS, eps=EPS, clamp=CLAMP)
    u = jnp.sum(h_pre[..., None] * x, axis=-2)
    return (np.asarray(u), np.asarray(X.mhc_merge(
        jnp.asarray(x), (h_post, h_res), jnp.asarray(y))), np.asarray(h_res))


@STREAMS
def test_the_wrap_is_its_equations(n):
    x, y, phi, alpha, b = _wrap_inputs(n)
    u, out, h_res = _program_wrap(x, y, phi, alpha, b)
    want_u, want, want_res = np_wrap(x, y, phi, alpha, *b)
    np.testing.assert_allclose(u, want_u, atol=2e-6)
    np.testing.assert_allclose(out, want, atol=1e-5)
    np.testing.assert_allclose(h_res, want_res, atol=2e-6)
    # doubly stochastic after 20 iterations (alpha_res 0.5 under order-1
    # biases: at 1.0 a token in some hundred is still 1e-3 off, which is
    # what mhc_defect_max is on the ledger for), and the program says so
    assert np.abs(h_res.sum(-1) - 1).max() < 1e-4
    assert np.abs(h_res.sum(-2) - 1).max() < 1e-4
    defect = float(X.mhc_defect(jnp.asarray(h_res)))
    assert defect == pytest.approx(max(np.abs(h_res.sum(-1) - 1).max(),
                                       np.abs(h_res.sum(-2) - 1).max()))
    # a masked token's matrix does not count
    bad = jnp.asarray(h_res).at[3].set(1.0)
    mask = jnp.arange(h_res.shape[0]) != 3
    assert float(X.mhc_defect(bad)) > 0.5
    assert float(X.mhc_defect(bad, mask)) == pytest.approx(defect, abs=1e-6)


@STREAMS
@pytest.mark.parametrize("wrong", [dict(iters=ITERS - 1),
                                   dict(rows_first=True), dict(clamp=None)],
                         ids=["19_iterations", "rows_first", "no_clamp"])
def test_a_wrong_wrap_fails_the_comparison(n, wrong):
    """Each departure moves ``H_res`` by more than the comparison allows:
    one iteration fewer, rows normalised before columns, and (with
    ``alpha_res hres`` pushed past 30, where the clamp binds) no clamp."""
    x, y, phi, alpha, b = _wrap_inputs(
        n, seed=1, scale_res=40.0 if "clamp" in wrong else 2.0)
    _, out, h_res = _program_wrap(x, y, phi, alpha, b)
    _, right, right_res = np_wrap(x, y, phi, alpha, *b)
    np.testing.assert_allclose(h_res, right_res, atol=5e-6)
    with np.errstate(over="ignore", invalid="ignore"):
        _, want, want_res = np_wrap(x, y, phi, alpha, *b, **wrong)
    assert not np.allclose(h_res, want_res, atol=1e-4)
    assert not np.allclose(out, want, atol=1e-4)
    np.testing.assert_allclose(out, right, atol=1e-4)


@STREAMS
def test_at_its_initialisation_the_wrap_is_a_plain_residual(n):
    """``H_pre`` reads the streams' mean, ``H_post`` is 1 and ``H_res`` the
    identity to 2e-3: each stream carries ``h + F(h)`` as one stream
    would."""
    hf, model, _ = build(n)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.ones((1, 8), jnp.int32))["params"]
    hc = params["backbone"]["layers_0"]["attn_hc"]
    x = jnp.asarray(np.random.default_rng(0).normal(size=(5, n, 32)),
                    jnp.float32)
    h_pre, h_post, h_res = X.mhc_maps(
        x, hc["phi"], hc["alpha"], hc["b_pre"], hc["b_post"], hc["b_res"],
        iters=ITERS, eps=EPS, clamp=CLAMP)
    np.testing.assert_allclose(h_pre, 1 / n, atol=2e-3)
    np.testing.assert_allclose(h_post, 1.0, atol=5e-3)
    np.testing.assert_allclose(h_res, np.broadcast_to(np.eye(n), h_res.shape),
                               atol=2e-3)


# -- (b) the gate -----------------------------------------------------------------

def np_gate(scores, bias, k, scale):
    """Top ``k`` of ``scores + bias``, ties to the lower index; weights
    the chosen scores renormalised to ``scale``."""
    sel = (scores + bias).astype(np.float32)
    ids = np.stack([np.lexsort((np.arange(len(row)), -row))[:k]
                    for row in sel])
    chosen = np.take_along_axis(scores, ids, axis=1).astype(np.float64)
    return ids, scale * chosen / (chosen.sum(-1, keepdims=True) + 1e-20)


def test_the_gate_is_its_equations():
    rng = np.random.default_rng(0)
    scores = (1 / (1 + np.exp(-rng.normal(size=(64, 16))))).astype(np.float32)
    bias = rng.normal(size=16).astype(np.float32)
    ids, w = moe.sigmoid_bias_gate(jnp.asarray(scores), jnp.asarray(bias),
                                   4, 2.0)
    want_ids, want_w = np_gate(scores, bias, 4, 2.0)
    np.testing.assert_array_equal(ids, want_ids)
    np.testing.assert_allclose(w, want_w, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(w).sum(-1), 2.0, rtol=1e-6)
    # the bias changes which experts are chosen ...
    plain_ids, plain_w = moe.sigmoid_bias_gate(
        jnp.asarray(scores), jnp.zeros(16), 4, 2.0)
    assert (np.sort(np.asarray(plain_ids), -1)
            != np.sort(np.asarray(ids), -1)).any(axis=-1).mean() > 0.5
    # ... and never a weight: a bias that keeps the choice keeps the
    # weights to the last bit, however large it is
    lifted_ids, lifted_w = moe.sigmoid_bias_gate(
        jnp.asarray(scores), jnp.full(16, 7.0), 4, 2.0)
    np.testing.assert_array_equal(lifted_ids, plain_ids)
    np.testing.assert_array_equal(lifted_w, plain_w)


def test_the_gate_breaks_ties_as_top_k_does():
    scores = np.full((3, 8), 0.5, np.float32)
    scores[1, 5] = 0.75
    scores[2, [6, 2]] = 0.25
    bias = np.zeros(8, np.float32)
    ids, w = moe.sigmoid_bias_gate(jnp.asarray(scores), jnp.asarray(bias),
                                   3, 2.0)
    np.testing.assert_array_equal(ids, [[0, 1, 2], [5, 0, 1], [0, 1, 3]])
    np.testing.assert_array_equal(ids, np_gate(scores, bias, 3, 2.0)[0])
    # the reference's rank-by-comparison gate chooses the same sets
    weights, chosen = reference._gate(jnp.asarray(scores), jnp.asarray(bias),
                                      3, 2.0)
    for row, picked in zip(np.asarray(chosen), np.asarray(ids)):
        assert sorted(np.flatnonzero(row)) == sorted(picked)
    np.testing.assert_allclose(np.asarray(weights).sum(-1), 2.0, rtol=1e-6)


def tiny_deepseek_v2():
    """The tiny DeepSeek-V2 model of ``tests/test_serve_latent.py``:
    ``(model, params)``."""
    cfg = D.DeepseekV2Config(
        vocab_size=128, hidden_size=32, num_layers=3, num_heads=2,
        q_lora_rank=12, kv_lora_rank=8, qk_nope_head_dim=8,
        qk_rope_head_dim=4, v_head_dim=8, intermediate_size=48,
        moe_intermediate_size=16, n_routed_experts=8, n_shared_experts=1,
        num_experts_per_tok=2, n_group=4, topk_group=2, experts_held=4,
        max_position_embeddings=128, eos_token_id=127, pad_token_id=0)
    model = D.DeepseekV2ForCausalLM(cfg)
    return model, model.init(jax.random.PRNGKey(0),
                             jnp.ones((1, 8), jnp.int32))["params"]


def test_deepseek_v2_is_left_as_it_was():
    """The tiny DeepSeek-V2 model of ``tests/test_serve_latent.py``: its
    parameter names and its logits as commit 1d6ca58 (the parent of PR 35)
    gave them. ``group_limited_gate`` is untouched, and the gate hook adds
    no parameter to this family."""
    golden = json.load(open(os.path.join(
        os.path.dirname(__file__), "data", "deepseek_v2_tiny_golden.json")))
    model, params = tiny_deepseek_v2()
    names = sorted("/".join(str(getattr(p, "key", p)) for p in path)
                   for path, _ in
                   jax.tree_util.tree_flatten_with_path(params)[0])
    assert names == golden["names"]
    tokens = jnp.asarray(np.random.RandomState(35).randint(
        1, 120, (2, 24)).astype(np.int32))
    logits = np.asarray(model.apply({"params": params}, tokens))
    np.testing.assert_allclose(logits[:, ::6, :8], golden["logits"],
                               atol=2e-6)


# -- (c) the model against the reference ------------------------------------------

@STREAMS
@pytest.mark.parametrize("length", [40, 1024])
def test_the_model_is_the_reference(n, length):
    hf, model, params = build(n, seed=n)
    tokens = jnp.asarray(np.random.default_rng(length).integers(
        3, hf["vocab_size"], size=length, dtype=np.int32))
    got, mut = model.apply({"params": params}, tokens[None],
                           mutable=[D.MOE_STATS])
    pad = -length % 512 if length > 512 else 0
    chosen, defects = [], []
    want = reference.logits(params, hf, jnp.pad(tokens, (0, pad)),
                            jnp.arange(length), routing_out=chosen,
                            defect_out=defects)
    assert float(jnp.abs(got).max()) > 0.1
    np.testing.assert_allclose(got[0], want, atol=2e-5)
    # the order-1 bias matters: without it the reference chooses otherwise
    assert len(chosen) == 2 and all(c.shape == (length + pad, 8)
                                    for c in chosen)
    flat = {"/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                     for p in path): leaf
            for path, leaf in jax.tree_util.tree_flatten_with_path(
                mut[D.MOE_STATS])[0]}
    ids = flat["backbone/layers_1/moe/expert_ids/0"][0]        # [S, k]
    mine = np.zeros((length, 8), bool)
    np.put_along_axis(mine, np.asarray(ids), True, axis=1)
    np.testing.assert_array_equal(mine, np.asarray(chosen[0])[:length])
    unbiased = jax.tree_util.tree_map_with_path(
        lambda path, leaf: jnp.zeros_like(leaf) if "e_score" in str(path[-1])
        else leaf, params)
    other = []
    reference.logits(unbiased, hf, jnp.pad(tokens, (0, pad)),
                     jnp.arange(length), routing_out=other)
    assert (np.asarray(other[0]) != np.asarray(chosen[0])).any(-1).mean() > 0.3
    # two wraps a layer sow their defect; the reference computes the same
    sown = [float(v) for k, v in sorted(flat.items()) if "mhc_defect" in k]
    assert len(sown) == 6
    if not pad:
        assert max(sown) == pytest.approx(max(float(d) for d in defects),
                                          rel=1e-3, abs=1e-7)


def test_logit_positions_pick_one_row_of_the_head():
    hf, model, params = build(4)
    tokens = jnp.asarray(np.random.default_rng(0).integers(
        3, 96, size=(3, 16), dtype=np.int32))
    whole = model.apply({"params": params}, tokens)
    at = jnp.asarray([0, 7, 15])
    one = model.apply({"params": params}, tokens, logit_positions=at)
    assert one.shape == (3, 1, 96) and model.takes_logit_positions
    np.testing.assert_allclose(one[:, 0], whole[jnp.arange(3), at],
                               atol=1e-6)


def test_the_reference_reads_nothing_of_the_programs_models():
    src = open(reference.__file__).read()
    assert "huggingface_sagemaker" not in src and "ragged_dot" not in src
    assert "import" in src and "chipbench.reference.deepseek_v2" in src


# -- (f) the loader ---------------------------------------------------------------

def test_the_family_is_found_by_its_model_type():
    hf = hf_config()
    assert detect_family(hf) == "xing4_0"
    assert CONFIG_BUILDERS["xing4_0"] is X.xing4_config_from_hf
    assert MODEL_REGISTRY[("xing4_0", "causal-lm")] is X.Xing4ForCausalLM
    cfg = X.xing4_config_from_hf(hf)
    assert (cfg.hc_mult, cfg.hc_sinkhorn_iters, cfg.num_moe_layers) == (4, 20, 2)
    # 192^-1/2 (0.1 ln 64 + 1)^2, and cos and sin times 1
    assert cfg.softmax_scale == pytest.approx(0.14468 * (192 / 12) ** 0.5,
                                              rel=1e-4)
    assert cfg.rope_factor == 1.0
    assert X.Xing4ForCausalLM(cfg).residual_kw() == {
        "residual_streams": 4, "gate": "sigmoid_bias"}


@pytest.mark.parametrize("key, value, words", [
    ("n_group", 8, "n_group=8"),
    ("topk_group", 2, "topk_group=2"),
    ("scoring_func", "softmax", "scoring_func='softmax'"),
    ("topk_method", "group_limited_greedy", "topk_method="),
    ("norm_topk_prob", False, "norm_topk_prob=False"),
    ("hc_mult", 1, "hc_mult 1"),
    ("q_lora_rank", None, "q_lora_rank"),
])
def test_the_loader_refuses_by_name_what_it_does_not_run(key, value, words):
    with pytest.raises(ValueError, match=words):
        X.xing4_config_from_hf(hf_config(**{key: value}))


def test_deepseek_v2s_refusal_names_the_family_that_has_the_gate():
    hf = dict(hf_config(), model_type="deepseek_v2")
    with pytest.raises(ValueError, match="scoring_func='sigmoid'.*xing4_0"):
        D.deepseek_v2_config_from_hf(hf)
