"""DeepSeek-V2 (ISSUE 28): latent attention in its two forms against each
other and against the benchmark's plain reference, chunked prefill and
decode through the cache against the full forward, the group-limited gate
against a brute-force gate, routing that depends on the token alone, the
shares of an expert-parallel deployment adding up to the uncut layer, and
YaRN's published numbers. All float32 on the CPU, where a matmul is
exact to rounding: tolerances are a few float32 ulps of values of order
one (1e-5), no more."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.reference import deepseek_v2 as R
from huggingface_sagemaker_tensorflow_distributed_tpu.models import (
    deepseek_v2 as D,
)
from huggingface_sagemaker_tensorflow_distributed_tpu.models import moe
from huggingface_sagemaker_tensorflow_distributed_tpu.models.llama import (
    _scaled_inv_freq,
    yarn_correction_range,
    yarn_mscale,
)

YARN = dict(type="yarn", factor=40, beta_fast=32, beta_slow=1, mscale=0.707,
            mscale_all_dim=0.707, original_max_position_embeddings=64)
TOL = 1e-5


def _cfg(**kw):
    base = dict(
        vocab_size=256, hidden_size=64, num_layers=2, num_heads=4,
        q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, intermediate_size=96,
        moe_intermediate_size=32, n_routed_experts=8, n_shared_experts=2,
        num_experts_per_tok=2, n_group=4, topk_group=2, experts_held=4,
        routed_scaling_factor=16.0, max_position_embeddings=2048,
        rope_scaling=tuple(sorted(YARN.items())))
    base.update(kw)
    return D.DeepseekV2Config(**base)


def _file_cfg(cfg):
    """The configuration FILE's keys, as the reference reads them."""
    return dict(
        num_attention_heads=cfg.num_heads, rms_norm_eps=cfg.rms_norm_eps,
        kv_lora_rank=cfg.kv_lora_rank,
        qk_nope_head_dim=cfg.qk_nope_head_dim,
        qk_rope_head_dim=cfg.qk_rope_head_dim, v_head_dim=cfg.v_head_dim,
        rope_scaling=cfg.rope_scaling_dict, rope_theta=cfg.rope_theta,
        n_routed_experts=cfg.held,
        expert_parallel=cfg.n_routed_experts // cfg.held,
        expert_rank=cfg.expert_rank, n_group=cfg.n_group,
        topk_group=cfg.topk_group,
        num_experts_per_tok=cfg.num_experts_per_tok,
        routed_scaling_factor=cfg.routed_scaling_factor,
        num_hidden_layers=cfg.num_layers)


@pytest.fixture(scope="module")
def tiny():
    cfg = _cfg()
    model = D.DeepseekV2ForCausalLM(cfg)
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 40), 3, 256)
    params = model.init(jax.random.PRNGKey(0), ids)["params"]
    return cfg, model, params, ids


# -- latent attention: two forms, one function --------------------------------

@pytest.mark.parametrize("heads,block", [(4, 64), (4, 16), (2, 8)],
                         ids=["one_block", "two_blocks", "four_blocks"])
@pytest.mark.parametrize("q_len", [1, 12], ids=["one_query", "chunk"])
def test_expanded_and_absorbed_attend_alike(heads, block, q_len):
    B, W, rank, nope, rot, vd = 2, 32, 16, 16, 8, 16
    k = jax.random.split(jax.random.PRNGKey(3), 5)
    q_nope = jax.random.normal(k[0], (B, q_len, heads, nope))
    q_pe = jax.random.normal(k[1], (B, q_len, heads, rot))
    latent = jnp.pad(jax.random.normal(k[2], (B, W, rank + rot)),
                     [(0, 0), (0, 0), (0, 128 - rank - rot)])
    w = jax.random.normal(k[3], (rank, heads, nope + vd)) * 0.3
    seen = (jnp.arange(W)[None, None, :]
            <= (W - q_len + jnp.arange(q_len))[None, :, None])
    bias = jnp.broadcast_to(jnp.where(seen, 0.0, D.NEG_INF), (B, q_len, W))
    a = D.attend_expanded(q_nope, q_pe, latent, bias, w, rank=rank,
                          scale=0.2, key_block=block)
    assert a.shape == (B, q_len, heads, vd)
    if q_len == 1:
        # the absorbed form takes one query a row, and a chunk never
        b = D.attend_absorbed(q_nope, q_pe, latent, bias, w, rank=rank,
                              scale=0.2)
        np.testing.assert_allclose(a, b, atol=3e-5)
    else:
        with pytest.raises(AssertionError, match="one query a row"):
            D.attend_absorbed(q_nope, q_pe, latent, bias, w, rank=rank,
                              scale=0.2)
    # and it is the plain softmax over all the keys at once
    kv = jnp.einsum("bwr,rhd->bwhd", latent[..., :rank], w)
    scores = (jnp.einsum("bshd,bwhd->bhsw", q_nope, kv[..., :nope])
              + jnp.einsum("bshd,bwd->bhsw", q_pe,
                           latent[..., rank:rank + rot])) * 0.2
    plain = jnp.einsum("bhsw,bwhd->bshd",
                       jax.nn.softmax(scores + bias[:, None], -1),
                       kv[..., nope:])
    np.testing.assert_allclose(a, plain, atol=3e-5)


@pytest.mark.parametrize("path", ["expanded", "absorbed"])
def test_the_model_by_each_form_is_the_reference(tiny, path):
    """``expanded``: the plain forward, forty queries a row; ``absorbed``:
    the same tokens one at a time through the cache, one query a row."""
    cfg, model, params, ids = tiny
    if path == "expanded":
        got = model.apply({"params": params}, ids)
    else:
        _, v = model.apply({"params": params}, jnp.ones((2, 40), jnp.int32),
                           decode=True, mutable=["cache"])
        outs = []
        for pos in range(40):
            lg, v = model.apply(
                {"params": params, "cache": v["cache"]}, ids[:, pos:pos + 1],
                position_ids=jnp.full((2, 1), pos), decode=True,
                mutable=["cache"])
            outs.append(lg)
        got = jnp.concatenate(outs, 1)
    for b in range(ids.shape[0]):
        want = R.logits(params, _file_cfg(cfg), ids[b], jnp.arange(40))
        np.testing.assert_allclose(got[b], want, atol=TOL)


def test_the_path_is_chosen_by_the_shape_of_the_call():
    assert D.latent_path(1) == "absorbed"
    assert D.latent_path(2) == D.latent_path(512) == "expanded"
    assert D.DeepseekV2ForCausalLM.latent_path(1) == "absorbed"


# -- the cache ----------------------------------------------------------------

@pytest.mark.parametrize("schedule", [(16, 16, 1, 1, 1, 5), (40,),
                                      (8, 1, 8, 1, 22)],
                         ids=["chunks_then_decode", "one_shot", "mixed"])
def test_prefill_then_decode_through_the_cache_is_the_full_forward(
        tiny, schedule):
    cfg, model, params, ids = tiny
    full = model.apply({"params": params}, ids)
    _, v = model.apply({"params": params}, jnp.ones((2, 48), jnp.int32),
                       decode=True, mutable=["cache"])
    cache = v["cache"]
    leaf = cache["backbone"]["layers_0"]["self_attn"]["cached_latent"]
    assert leaf.shape == (2, 1, 48, D.latent_width(cfg)) == (2, 1, 48, 128)
    outs, pos = [], 0
    for n in schedule:
        lg, v = model.apply(
            {"params": params, "cache": cache}, ids[:, pos:pos + n],
            position_ids=jnp.broadcast_to(pos + jnp.arange(n), (2, n)),
            decode=True, mutable=["cache"])
        cache = v["cache"]
        outs.append(lg)
        pos += n
    np.testing.assert_allclose(jnp.concatenate(outs, 1), full, atol=TOL)
    # what the cache holds: c | k_pe | zeros, nothing per head
    row = cache["backbone"]["layers_1"]["self_attn"]["cached_latent"]
    assert float(jnp.abs(row[..., :40, :24]).min()) > 0
    assert float(jnp.abs(row[..., 24:]).max()) == 0


# -- the gate -----------------------------------------------------------------

def _brute_gate(probs, n_group, topk_group, top_k, scale):
    """Groups by their best expert, the best groups kept, the top
    experts of what is left, ties to the lower index; numpy, a loop."""
    T, E = probs.shape
    size = E // n_group
    ids = np.zeros((T, top_k), np.int64)
    weights = np.zeros((T, top_k), np.float32)
    for t in range(T):
        best = [probs[t, g * size:(g + 1) * size].max()
                for g in range(n_group)]
        kept = sorted(range(n_group), key=lambda g: (-best[g], g))[:topk_group]
        left = np.array([probs[t, e] if e // size in kept else 0.0
                         for e in range(E)], np.float32)
        order = sorted(range(E), key=lambda e: (-left[e], e))[:top_k]
        ids[t], weights[t] = order, left[order] * scale
    return ids, weights


@pytest.mark.parametrize("E,n_group,topk_group,top_k", [
    (160, 8, 3, 6), (8, 4, 2, 2), (64, 8, 1, 4), (16, 1, 1, 3)],
    ids=["published", "rehearsal", "one_group_kept", "no_groups"])
def test_gate_against_a_brute_force_gate(E, n_group, topk_group, top_k):
    logits = np.random.RandomState(E).randn(96, E).astype(np.float32) * 2
    probs = np.asarray(jax.nn.softmax(jnp.asarray(logits), -1))
    ids, w = moe.group_limited_gate(jnp.asarray(probs), n_group, topk_group,
                                    top_k, 16.0)
    want_ids, want_w = _brute_gate(probs, n_group, topk_group, top_k, 16.0)
    np.testing.assert_array_equal(ids, want_ids)
    np.testing.assert_allclose(w, want_w, rtol=1e-6)
    # times 16, never renormalised: the weights are the probabilities
    np.testing.assert_allclose(
        np.asarray(w) / 16.0, np.take_along_axis(probs, want_ids, 1),
        rtol=1e-6)
    # and the reference's own gate says the same
    dense = np.asarray(R._gate(jnp.asarray(probs), n_group, topk_group,
                               top_k, 16.0))
    mine = np.zeros_like(dense)
    np.put_along_axis(mine, want_ids, want_w, 1)
    np.testing.assert_allclose(dense, mine, rtol=1e-6)


def test_gate_ties_go_to_the_lower_index():
    probs = jnp.full((3, 16), 1.0 / 16)
    ids, w = moe.group_limited_gate(probs, 4, 2, 3, 16.0)
    np.testing.assert_array_equal(ids, [[0, 1, 2]] * 3)   # groups 0, 1 kept
    np.testing.assert_allclose(w, 1.0)


# -- routing depends on the token alone ---------------------------------------

def _moe_layer(cfg, params, x, token_mask=None):
    return D.DeepseekV2MoE(cfg).apply(
        {"params": params}, x, token_mask, mutable=[D.MOE_STATS])


@pytest.mark.parametrize("chunk", [1, 8, 24])
def test_a_chunk_routes_as_the_same_tokens_one_shot(chunk):
    cfg = _cfg()
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 48, 64))
    params = D.DeepseekV2MoE(cfg).init(jax.random.PRNGKey(6), x)["params"]
    whole, stats = _moe_layer(cfg, params, x)
    parts, counts = [], 0
    for s in range(0, 48, chunk):
        y, st = _moe_layer(cfg, params, x[:, s:s + chunk])
        parts.append(y)
        counts = counts + st[D.MOE_STATS]["expert_counts"][0]
    np.testing.assert_allclose(jnp.concatenate(parts, 1), whole, atol=TOL)
    np.testing.assert_array_equal(counts,
                                  stats[D.MOE_STATS]["expert_counts"][0])


def test_counts_leave_out_the_tokens_masked_as_pads():
    cfg = _cfg()
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 16, 64))
    params = D.DeepseekV2MoE(cfg).init(jax.random.PRNGKey(6), x)["params"]
    mask = jnp.arange(16)[None, :] < jnp.array([16, 5])[:, None]
    y_all, st_all = _moe_layer(cfg, params, x)
    y, st = _moe_layer(cfg, params, x, mask)
    np.testing.assert_array_equal(y, y_all)      # the mask moves no output
    _, st_real = _moe_layer(cfg, params,
                            jnp.concatenate([x[0], x[1, :5]])[None])
    np.testing.assert_array_equal(
        st[D.MOE_STATS]["expert_counts"][0],
        st_real[D.MOE_STATS]["expert_counts"][0])
    assert int(st_all[D.MOE_STATS]["expert_counts"][0].sum()) >= int(
        st[D.MOE_STATS]["expert_counts"][0].sum())


# -- the shares of a deployment add up to the uncut layer ---------------------

@pytest.mark.parametrize("shares", [1, 2, 4])
def test_the_shares_routed_parts_and_the_shared_expert_once_are_the_layer(
        shares):
    whole = _cfg(experts_held=None)
    x = jax.random.normal(jax.random.PRNGKey(7), (1, 64, 64))
    params = D.DeepseekV2MoE(whole).init(jax.random.PRNGKey(8), x)["params"]
    h = x[0]
    sh = params["shared_experts"]
    shared = R._swiglu(h, sh["gate_proj"]["kernel"], sh["up_proj"]["kernel"],
                       sh["down_proj"]["kernel"])
    # the uncut reference layer: every expert over every token, masked
    probs = jax.nn.softmax(h @ params["router"], -1)
    gates = R._gate(probs, whole.n_group, whole.topk_group,
                    whole.num_experts_per_tok, whole.routed_scaling_factor)
    want = shared + sum(
        gates[:, e:e + 1] * R._swiglu(h, params["experts_gate_proj"][e],
                                      params["experts_up_proj"][e],
                                      params["experts_down_proj"][e])
        for e in range(whole.n_routed_experts))
    held = whole.n_routed_experts // shares
    routed, total = 0.0, 0
    for rank in range(shares):
        cfg = dataclasses.replace(whole, experts_held=held, expert_rank=rank)
        cut = dict(params, **{k: params[k][rank * held:(rank + 1) * held]
                              for k in ("experts_gate_proj",
                                        "experts_up_proj",
                                        "experts_down_proj")})
        y, st = _moe_layer(cfg, cut, x)
        routed = routed + (y[0] - shared)
        total += int(st[D.MOE_STATS]["expert_counts"][0].sum())
    np.testing.assert_allclose(routed + shared, want, atol=3e-5)
    assert total == 64 * whole.num_experts_per_tok   # nothing dropped


def test_a_share_must_divide_the_experts():
    with pytest.raises(ValueError, match="must divide"):
        _cfg(experts_held=3)
    with pytest.raises(ValueError, match="expert_rank"):
        _cfg(experts_held=4, expert_rank=2)


# -- YaRN ---------------------------------------------------------------------

PUBLISHED = dict(type="yarn", factor=40, beta_fast=32, beta_slow=1,
                 mscale=0.707, mscale_all_dim=0.707,
                 original_max_position_embeddings=4096)


def test_yarn_published_numbers():
    assert yarn_correction_range(PUBLISHED, 64, 10000.0) == (10, 23)
    m = yarn_mscale(40, 0.707)
    assert m == pytest.approx(1.2608, abs=1e-4)
    cfg = D.DeepseekV2Config(rope_scaling=tuple(sorted(PUBLISHED.items())))
    assert cfg.softmax_scale == pytest.approx(0.11472, abs=1e-5)
    assert cfg.rope_factor == 1.0
    assert D.latent_width(cfg) == 640 and cfg.num_moe_layers == 59


def test_yarn_frequencies_keep_blend_and_interpolate():
    f = 10000.0 ** (-np.arange(0, 64, 2, dtype=np.float32) / 64)
    got = np.asarray(_scaled_inv_freq(jnp.asarray(f), PUBLISHED, 10000.0))
    ramp = np.clip((np.arange(32) - 10) / 13.0, 0, 1)
    np.testing.assert_allclose(got, f / 40 * ramp + f * (1 - ramp),
                               rtol=1e-6)
    np.testing.assert_allclose(got[:11], f[:11], rtol=1e-6)      # kept
    np.testing.assert_allclose(got[23:], f[23:] / 40, rtol=1e-6)  # slowed
    np.testing.assert_allclose(
        got, np.asarray(R._yarn_inv_freq(64, 10000.0, PUBLISHED)), rtol=1e-6)


@pytest.mark.parametrize("key,value", [
    ("scoring_func", "sigmoid"), ("topk_method", "noaux_tc"),
    ("norm_topk_prob", True), ("moe_layer_freq", 2),
    ("rope_scaling", {"type": "linear", "factor": 2}),
    ("q_lora_rank", None)])
def test_the_loader_refuses_what_the_modules_do_not_compute(key, value):
    import json
    import os

    from chipbench import spec
    from chipbench.families.deepseek_v2 import program_config

    hf = program_config(spec.load_json(os.path.join(
        spec.HERE, "configs", "deepseek-v2-ep4.json")))
    assert D.deepseek_v2_config_from_hf(hf).held == 40
    with pytest.raises(ValueError, match="not implemented"):
        D.deepseek_v2_config_from_hf(dict(hf, **{key: value}))
    assert json.dumps(hf)        # a plain mapping, as config.json is
