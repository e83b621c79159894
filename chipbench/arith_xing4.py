"""Operations and bytes of the Xing4 configuration's own parts, from the
configuration FILE's shapes (``chipbench/configs/xing4.0-29b-a4b-pp7.json``).
Attention, the experts, the head and a whole decode step or prefill
dispatch are ``arith_deepseek_v2.py``'s (the same layout under the same
keys: ``n_routed_experts`` held of ``x expert_parallel``); what that file
leaves out of this model is the residual path's maps, counted here: 4.1 M
parameters and 0.5% of a token's FLOPs, so a share computed without them
can only read low. Matmul FLOPs only, bf16 bytes; what a call NEEDS.
"""

from __future__ import annotations

from chipbench import arith_deepseek_v2 as base

ITEM = base.ITEM


def map_params(c: dict) -> int:
    """One sub-layer's ``Phi``: the ``hc_mult`` streams' values by the
    ``n + n + n^2`` outputs of the three maps."""
    n = c["hc_mult"]
    return n * c["hidden_size"] * (2 * n + n * n)


def wrap_params(c: dict) -> int:
    """All of the residual path: two wraps a layer, each ``Phi``, three
    ``alpha`` and the biases of the three maps."""
    n = c["hc_mult"]
    return 2 * c["num_hidden_layers"] * (map_params(c) + 3 + 2 * n + n * n)


def norm_params(c: dict) -> int:
    """The RMSNorm scales: two a layer over the hidden size, the query
    and key/value latents' a layer, the final one."""
    return (c["num_hidden_layers"] * (2 * c["hidden_size"] + c["q_lora_rank"]
                                      + c["kv_lora_rank"])
            + c["hidden_size"])


def total_params(c: dict) -> int:
    """Every parameter held: what a token passes whatever its routing,
    the held experts, the embedding, the gates' selection biases, the
    norms and the residual path."""
    _, moe = base.layer_counts(c)
    return (base.fixed_params(c)
            + moe * c["n_routed_experts"] * base.expert_params(c)
            + c["hidden_size"] * c["vocab_size"]
            + moe * c["n_routed_experts"] * c["expert_parallel"]
            + norm_params(c) + wrap_params(c))


def latent_row_bytes(c: dict) -> int:
    """One token's latent row of ONE layer as the pool stores it:
    ``kv_lora_rank + qk_rope_head_dim`` values rounded up to whole
    128-lane tiles (640 for the published 576)."""
    width = -(-(c["kv_lora_rank"] + c["qk_rope_head_dim"]) // 128) * 128
    return ITEM * width


def paged_latent_decode_need(c: dict, keys: float) -> dict:
    """FLOPs and bytes ONE layer-call of the fused paged latent kernel
    needs over ``keys`` resident tokens in all (the sum over the step's
    slots): every row read once as stored; a key's score against the
    stored row and its share of the weighted sum over ``kv_lora_rank``,
    for every head. The queries in and the outputs out (one row a head a
    slot) are under a hundredth of the rows' bytes and are left out."""
    width = latent_row_bytes(c) // ITEM
    return {"flops": 2.0 * keys * c["num_attention_heads"]
            * (width + c["kv_lora_rank"]),
            "bytes": float(keys) * latent_row_bytes(c)}
