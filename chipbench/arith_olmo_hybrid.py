"""Operations and bytes of the Olmo-Hybrid configuration's steps, from the
configuration FILE's shapes (``chipbench/configs/olmo-hybrid-7b-pp2.json``).
Matmul FLOPs only (2 x multiply-adds), bf16 weights and K/V, float32
recurrent state; what a step NEEDS, not what the program does: weights
read once, a resident token's K/V read once, a slot's state read once and
written once, no padding, no pad row.
"""

from __future__ import annotations

ITEM = 2          # bytes of a bf16 value
STATE_ITEM = 4    # bytes of a float32 state value
CHUNK = 64        # the chunked form's chunk (ops/gated_delta.py)


def layer_counts(c: dict) -> tuple:
    """(linear layers, full layers) as held."""
    kinds = c["layer_types"][:c["num_hidden_layers"]]
    linear = sum(1 for k in kinds if k == "linear_attention")
    return linear, len(kinds) - linear


def _linear_dims(c: dict) -> tuple:
    return (c["linear_num_key_heads"], c["linear_key_head_dim"],
            c["linear_value_head_dim"])


def linear_mixer_params(c: dict) -> int:
    """One linear-attention layer's mixer: q, k, v, gate and output
    projections, the two per-head projections (a, b), the depthwise
    convolution, A_log, dt_bias and the gated norm's scale."""
    h, (heads, dk, dv) = c["hidden_size"], _linear_dims(c)
    return (h * heads * (2 * dk + 3 * dv) + 2 * h * heads
            + c["linear_conv_kernel_dim"] * heads * (2 * dk + dv)
            + 2 * heads + dv)


def full_mixer_params(c: dict) -> int:
    """One full layer's attention: q, k, v, o and the two norms over the
    whole query and key projections."""
    h, heads, kv = (c["hidden_size"], c["num_attention_heads"],
                    c["num_key_value_heads"])
    d = h // heads
    return h * d * (2 * heads + 2 * kv) + d * (heads + kv)


def mlp_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["intermediate_size"]


def total_params(c: dict) -> int:
    """Every parameter held: the layers with their two norms each, the
    embedding, the head, the final norm."""
    linear, full = layer_counts(c)
    h = c["hidden_size"]
    head = 0 if c.get("tie_word_embeddings") else h * c["vocab_size"]
    return (linear * linear_mixer_params(c) + full * full_mixer_params(c)
            + (linear + full) * (mlp_params(c) + 2 * h)
            + h * c["vocab_size"] + head + h)


def step_weight_params(c: dict) -> int:
    """Parameters a decode step reads: everything but the embedding table
    (a step reads one row of it a slot)."""
    return total_params(c) - c["hidden_size"] * c["vocab_size"]


def kv_token_bytes(c: dict) -> int:
    """K/V bytes of one resident token: K and V of every full layer."""
    _, full = layer_counts(c)
    d = c["hidden_size"] // c["num_attention_heads"]
    return full * 2 * c["num_key_value_heads"] * d * ITEM


def recurrent_state_bytes(c: dict) -> int:
    """Float32 bytes of one slot's recurrent state ``S`` over the linear
    layers."""
    linear, _ = layer_counts(c)
    heads, dk, dv = _linear_dims(c)
    return linear * heads * dk * dv * STATE_ITEM


def conv_tail_bytes(c: dict) -> int:
    """bf16 bytes of one slot's convolution tails over the linear layers:
    the last ``K - 1`` rows before the convolution."""
    linear, _ = layer_counts(c)
    heads, dk, dv = _linear_dims(c)
    return (linear * (c["linear_conv_kernel_dim"] - 1)
            * heads * (2 * dk + dv) * ITEM)


def state_bytes_per_slot(c: dict) -> int:
    return recurrent_state_bytes(c) + conv_tail_bytes(c)


def decode_step_need_bytes(c: dict, slots: float, kv_tokens: float,
                           conv_tails: bool = True) -> dict:
    """Bytes ONE decode step needs, by part: the weights once, the K/V of
    ``kv_tokens`` resident tokens, and ``slots`` slots' state read and
    written."""
    state = 2.0 * slots * recurrent_state_bytes(c)
    tails = 2.0 * slots * conv_tail_bytes(c) if conv_tails else 0.0
    parts = {"weights": float(ITEM * step_weight_params(c)),
             "state": state, "conv_tails": tails,
             "kv": float(kv_tokens) * kv_token_bytes(c)}
    parts["total"] = sum(parts.values())
    return parts


def recurrence_flops_per_token(c: dict) -> float:
    """One linear layer's recurrence for one token in the chunked form at
    ``CHUNK``: the chunk's ``K_b K^T`` and ``Q K^T`` (``CHUNK * dk`` each),
    the unit-triangular solve against ``dk + dv`` columns (``CHUNK / 2``
    rows each on average), the state read three times (``W S``, ``Q S``
    and the update, ``dk * dv`` each) and the chunk's scores times the
    new values (``CHUNK * dv``)."""
    heads, dk, dv = _linear_dims(c)
    return 2.0 * heads * (2 * CHUNK * dk + CHUNK / 2 * (dk + dv)
                          + 3 * dk * dv + CHUNK * dv)


def prefill_need_flops(c: dict, tokens: float, rows: float,
                       keys_needed: float, chunk: int) -> float:
    """FLOPs the REAL tokens of prefill dispatches need: ``tokens`` real
    prompt tokens in ``rows`` real chunk rows whose chunks had to see
    ``keys_needed`` keys in all (sum over rows of start + chunk): every
    projection and MLP a token, one head row a chunk row, causal full
    attention over the needed keys, the recurrence's chunked form."""
    linear, full = layer_counts(c)
    h = c["hidden_size"]
    head = h * c["vocab_size"]
    matmul = step_weight_params(c) - head
    weights = 2.0 * tokens * matmul + 2.0 * rows * head
    # causal: a row's queries see start + i + 1 keys, in all
    # chunk * keys - chunk^2 / 2 query-key pairs a row; QK^T and PV
    pairs = max(chunk * keys_needed - rows * chunk * chunk / 2.0, 0.0)
    attend = full * 2.0 * pairs * 2 * h
    return weights + attend + linear * tokens * recurrence_flops_per_token(c)
