"""Operations and bytes of the DeepSeek-V2 configuration's steps, from
the configuration FILE's shapes (``chipbench/configs/deepseek-v2-ep4.json``:
``n_routed_experts`` there counts the experts HELD). Matmul FLOPs only
(2 x multiply-adds), bf16 bytes; what a step NEEDS, not what the program
does: weights read once, a latent row read once, no padding, no pad row.
"""

from __future__ import annotations

ITEM = 2    # bytes of a bf16 value


def attention_params(c: dict) -> int:
    """One layer's latent attention: q_a, q_b, kv_a, kv_b, o."""
    h, heads = c["hidden_size"], c["num_attention_heads"]
    qk = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    return (h * c["q_lora_rank"] + c["q_lora_rank"] * heads * qk
            + h * (c["kv_lora_rank"] + c["qk_rope_head_dim"])
            + c["kv_lora_rank"] * heads * (c["qk_nope_head_dim"]
                                           + c["v_head_dim"])
            + heads * c["v_head_dim"] * h)


def expert_params(c: dict) -> int:
    """One routed expert: gate, up, down."""
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def layer_counts(c: dict) -> tuple:
    """(dense layers, expert layers) as held."""
    dense = min(c["first_k_dense_replace"], c["num_hidden_layers"])
    return dense, c["num_hidden_layers"] - dense


def fixed_params(c: dict) -> int:
    """Every parameter a token passes whatever its routing: attention of
    all layers, the dense layers' SwiGLU, the shared experts and the
    router of the expert layers, the head. (Norms and the token's one
    embedding row are left out: under a thousandth.)"""
    dense, moe = layer_counts(c)
    h = c["hidden_size"]
    return ((dense + moe) * attention_params(c)
            + dense * 3 * h * c["intermediate_size"]
            + moe * (c["n_shared_experts"] * expert_params(c)
                     + h * c["n_routed_experts"] * c["expert_parallel"])
            + h * c["vocab_size"])


def latent_token_bytes(c: dict) -> int:
    """Cache bytes of one token: a latent row a layer."""
    return (c["num_hidden_layers"] * ITEM
            * (c["kv_lora_rank"] + c["qk_rope_head_dim"]))


def absorbed_attention_flops(c: dict, keys: float) -> float:
    """One query row of one layer, absorbed form, against ``keys`` latent
    rows: fold W_uk into the query, scores over rank + rope, the weighted
    sum over rank, W_uv after it."""
    heads, r = c["num_attention_heads"], c["kv_lora_rank"]
    return 2.0 * heads * (c["qk_nope_head_dim"] * r
                          + keys * (r + c["qk_rope_head_dim"])
                          + keys * r + r * c["v_head_dim"])


def decode_step_need(c: dict, slots: float, live_tokens: float,
                     experts_touched: float, pairs_held: float) -> dict:
    """FLOPs and bytes ONE decode step needs: ``slots`` active rows over
    ``live_tokens`` resident tokens in all, ``experts_touched`` held
    experts (summed over the expert layers) that got a pair,
    ``pairs_held`` (token, expert) pairs on them."""
    layers = c["num_hidden_layers"]
    nbytes = (ITEM * (fixed_params(c) + experts_touched * expert_params(c))
              + live_tokens * latent_token_bytes(c))
    flops = (2.0 * slots * fixed_params(c)
             + 2.0 * pairs_held * expert_params(c)
             + layers * (slots * absorbed_attention_flops(c, 0)
                         + (live_tokens) * 2.0 * c["num_attention_heads"]
                         * (2 * c["kv_lora_rank"] + c["qk_rope_head_dim"])))
    return {"flops": flops, "bytes": nbytes}


def prefill_need_flops(c: dict, tokens: float, rows: float,
                       keys_needed: float, pairs_held: float,
                       chunk: int) -> float:
    """FLOPs the REAL tokens of prefill dispatches need: ``tokens`` real
    prompt tokens in ``rows`` real chunk rows whose chunks had to see
    ``keys_needed`` keys in all (sum over rows of start + chunk),
    ``pairs_held`` pairs on held experts. Attention in the EXPANDED form,
    the cheaper one for a chunk: every needed key expanded once a row
    through W_kvb, then causal scores and sums per head. The head is
    needed for one token a row at most."""
    heads = c["num_attention_heads"]
    layers = c["num_hidden_layers"]
    weights = (2.0 * tokens * (fixed_params(c)
                               - c["hidden_size"] * c["vocab_size"])
               + 2.0 * rows * c["hidden_size"] * c["vocab_size"]
               + 2.0 * pairs_held * expert_params(c))
    expand = 2.0 * keys_needed * c["kv_lora_rank"] * heads * (
        c["qk_nope_head_dim"] + c["v_head_dim"])
    # causal: a row's queries see start + i + 1 keys, in all
    # chunk * keys - chunk^2 / 2 query-key pairs a row
    pairs = max(chunk * keys_needed - rows * chunk * chunk / 2.0, 0.0)
    attend = 2.0 * pairs * heads * (c["qk_nope_head_dim"]
                                    + c["qk_rope_head_dim"]
                                    + c["v_head_dim"])
    return weights + layers * (expand + attend)
