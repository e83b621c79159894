"""Mixture-of-Experts feed-forward with expert parallelism.

Beyond-parity capability (the reference has no MoE or expert
parallelism, SURVEY.md §2 parallelism inventory): a GShard/Switch-style
token-routed MoE FFN designed TPU-first —

- **Dense dispatch/combine einsums**, no scatter/gather: routing is
  expressed as one-hot dispatch tensors contracted on the MXU, with a
  static per-expert capacity. It is the formulation the TRAINING families
  use (encoder MoE, Mixtral); it is not the only one with static shapes:
  :func:`dropless_experts` below sorts the (token, expert) pairs by
  expert and runs grouped matmuls over them, drops nothing, and is what
  the served DeepSeek-V2 and Xing4 families route with.
- **Expert parallelism via sharding annotations**: expert weights carry
  ``PartitionSpec("expert", ...)`` (``parallel/sharding.py``) and the
  dispatched activations are constrained expert-major, so XLA inserts
  the token all-to-alls over the ``expert`` mesh axis — no hand-written
  collectives, same ambient-distribution stance as the rest of the
  framework.
- **Static capacity**: each expert processes a fixed ``capacity`` slots
  per group (batch row); over-capacity tokens fall through on the
  residual path (standard GShard semantics, no dynamic shapes).

The router computes in fp32 (softmax over expert logits is precision
-sensitive); expert matmuls run in the model compute dtype (bf16 on
TPU). The Switch load-balance auxiliary loss is sowed into the
``losses`` collection; the Trainer adds every sowed value to the task
loss (``train/trainer.py``).

**Dropless routed experts** (:func:`group_limited_gate` or
:func:`sigmoid_bias_gate`, then :func:`dropless_experts`): a token's
routing depends on that token alone
(no capacity, no slot competition), so chunked prefill, one-shot prefill
and decode route alike, which is what lets the serving engine take the
layer. The layer is told which experts it holds (one chip's share of an
expert-parallel deployment): it routes over ALL experts and computes the
part of the result its own experts give.
"""

from __future__ import annotations

import math

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from huggingface_sagemaker_tensorflow_distributed_tpu.models.layers import ACT2FN, EncoderConfig


def expert_capacity(cfg: EncoderConfig, seq_len: int) -> int:
    """Static per-group expert capacity: ceil(k·S·factor / E), rounded up
    to a multiple of 4 so the slot dim tiles onto the VPU lanes."""
    raw = cfg.expert_capacity_factor * cfg.expert_top_k * seq_len / cfg.num_experts
    return max(4, 4 * math.ceil(raw / 4))


def _constrain(x, *spec):
    from huggingface_sagemaker_tensorflow_distributed_tpu.parallel.sharding import (
        constrain_if_mesh,
    )

    return constrain_if_mesh(x, *spec)


def topk_dispatch(probs, k: int, C: int, causal: bool):
    """Greedy top-k routing → capacity-slot dispatch, shared by every
    MoE flavor (Switch/GShard encoder FFN and Mixtral SwiGLU).

    Returns ``(combine [B,S,E,C] fp32, top1_mask [B,S,E])`` where
    ``combine`` carries each kept token→slot assignment weighted by its
    gate, normalized per token over its total selected top-k mass
    (Mixtral/HF convention — capacity-dropped choices keep zero
    dispatch and the token rides the residual). Slot priority is
    round-major (GShard) or position-major (``causal=True``, see
    ``MoeFeedForward`` docstring for why causal LMs need it).
    """
    B, S, E = probs.shape
    remaining = probs
    masks, gates = [], []
    for _ in range(k):
        idx = jnp.argmax(remaining, axis=-1)                   # [B,S]
        mask = jax.nn.one_hot(idx, E, dtype=jnp.float32)       # [B,S,E]
        gates.append(jnp.sum(remaining * mask, axis=-1))       # [B,S]
        remaining = remaining * (1.0 - mask)
        masks.append(mask)
    top1_mask = masks[0]

    if causal:
        # position-major: slot = #assignments to the chosen expert
        # from strictly-earlier tokens (any round). Rounds of one
        # token hit distinct experts, so slots stay collision-free,
        # and nothing about token i depends on tokens j > i.
        total = sum(masks)                                     # [B,S,E]
        prefix = jnp.cumsum(total, axis=1) - total
        slot_pos = [prefix] * k
    else:
        # round-major (GShard): all round-r slots precede round-r+1
        slot_pos = []
        counts = jnp.zeros((B, E), jnp.float32)
        for mask in masks:
            slot_pos.append(
                jnp.cumsum(mask, axis=1) - 1.0 + counts[:, None, :])
            counts = counts + jnp.sum(mask, axis=1)

    combine = jnp.zeros((B, S, E, C), jnp.float32)
    gate_total = jnp.zeros((B, S), jnp.float32)
    for mask, gate, pos in zip(masks, gates, slot_pos):
        slot = jnp.sum(pos * mask, axis=-1)                    # [B,S]
        kept = (slot < C) & (gate > 0.0)
        slot_oh = jax.nn.one_hot(jnp.where(kept, slot, 0).astype(jnp.int32),
                                 C, dtype=jnp.float32)         # [B,S,C]
        disp = (mask[..., None] * slot_oh[:, :, None, :]
                * kept[:, :, None, None].astype(jnp.float32))  # [B,S,E,C]
        combine = combine + gate[:, :, None, None] * disp
        gate_total = gate_total + gate

    denom = jnp.where(gate_total > 0.0, gate_total, 1.0)
    return combine / denom[:, :, None, None], top1_mask


def _route_and_dispatch(module: nn.Module, hidden, cfg, causal: bool):
    """The scaffolding every MoE flavor shares: fp32 router + softmax,
    :func:`topk_dispatch`, the Switch aux-loss sow, and the token→expert
    all-to-all (dispatch einsum + expert-major sharding constraint).
    Returns ``(expert_in [E,B,C,H], combine [B,S,E,C] fp32,
    non_expert_axes)``; the caller runs its expert FFN on ``expert_in``
    and combines with ``combine``. One implementation so router
    precision, the aux formula, and the sharding constraints cannot
    drift between the encoder MoE and Mixtral."""
    from huggingface_sagemaker_tensorflow_distributed_tpu.parallel.mesh import (
        AXIS_EXPERT,
        data_axis_names,
    )

    E, k = cfg.num_experts, cfg.expert_top_k
    _, S, H = hidden.shape
    C = expert_capacity(cfg, S)

    router = module.param(
        "router", nn.initializers.normal(cfg.initializer_range), (H, E),
        jnp.float32)
    # fp32 router: logits/softmax precision decides routing stability
    logits = jnp.einsum("bsh,he->bse", hidden.astype(jnp.float32), router)
    probs = jax.nn.softmax(logits, axis=-1)                    # [B,S,E]

    combine, top1_mask = topk_dispatch(probs, k, C, causal)
    dispatch = (combine > 0.0).astype(cfg.dtype)               # [B,S,E,C]

    # Switch load-balance loss (top-1 fractions × mean probs)
    frac = jnp.mean(top1_mask, axis=(0, 1))                    # [E]
    mean_prob = jnp.mean(probs, axis=(0, 1))                   # [E]
    aux = cfg.router_aux_coef * E * jnp.sum(frac * mean_prob)
    module.sow("losses", "moe_aux", aux)

    # [E,B,C,H]: E sharded over ``expert``, B over the other data
    # axes — the resharding from token-major is the all-to-all
    non_expert_axes = tuple(a for a in data_axis_names()
                            if a != AXIS_EXPERT)
    expert_in = jnp.einsum("bsec,bsh->ebch", dispatch,
                           hidden.astype(cfg.dtype))
    expert_in = _constrain(expert_in, AXIS_EXPERT, non_expert_axes)
    return expert_in, combine, non_expert_axes


class MoeFeedForward(nn.Module):
    """Drop-in replacement for ``FeedForward`` on MoE layers.

    Input/output: [batch, seq, hidden]. Each batch row is a routing
    group (tokens compete for expert slots within their own row — keeps
    the dispatch tensor O(S·E·C) per row and routing independent of the
    data sharding).

    Capacity-slot priority has two modes:

    - bidirectional (default): round-major, GShard-style — every top-1
      choice outranks any top-2 choice, so congestion preferentially
      drops second choices.
    - ``causal=True``: position-major — a token's slot index counts only
      assignments from strictly-earlier tokens (any round). Required for
      causal LMs: under round-major priority, whether token i's
      second-choice slot survives depends on the top-1 routing of tokens
      j > i, which leaks future-token information through the capacity
      drop pattern. (Capacity drops themselves remain a train-time-only
      phenomenon: incremental decode processes one token with no slot
      competition — the standard capacity-MoE asymmetry.)

    ``out_init_std`` overrides the output-projection init so residual
    -flow conventions (e.g. GPT-2's 1/sqrt(2·n_layer) scaling on every
    residual write) carry over to the expert bank.
    """

    config: EncoderConfig
    causal: bool = False
    out_init_std: float | None = None

    @nn.compact
    def __call__(self, hidden, deterministic: bool = True):
        from huggingface_sagemaker_tensorflow_distributed_tpu.parallel.mesh import (
            AXIS_EXPERT,
        )

        cfg = self.config
        E = cfg.num_experts
        _, _, H = hidden.shape
        F = cfg.intermediate_size

        expert_in, combine, non_expert_axes = _route_and_dispatch(
            self, hidden, cfg, self.causal)

        wi = self.param("wi", nn.initializers.normal(cfg.initializer_range),
                        (E, H, F), cfg.param_dtype)
        wo = self.param(
            "wo",
            nn.initializers.normal(self.out_init_std
                                   if self.out_init_std is not None
                                   else cfg.initializer_range),
            (E, F, H), cfg.param_dtype)
        h = jnp.einsum("ebch,ehf->ebcf", expert_in, wi.astype(cfg.dtype))
        h = ACT2FN[cfg.hidden_act](h)
        out = jnp.einsum("ebcf,efh->ebch", h, wo.astype(cfg.dtype))
        out = _constrain(out, AXIS_EXPERT, non_expert_axes)

        y = jnp.einsum("bsec,ebch->bsh", combine.astype(cfg.dtype), out)
        y = nn.Dropout(cfg.hidden_dropout)(y, deterministic=deterministic)
        return y


class MixtralMoeBlock(nn.Module):
    """Mixtral-style sparse MoE for the Llama family: SwiGLU experts
    (``w2(silu(w1 x) * w3 x)``, HF ``MixtralBlockSparseTop2MLP`` naming)
    behind the same dense-dispatch top-k router as ``MoeFeedForward``.

    HF parity notes (``MixtralSparseMoeBlock``):
    - the router (``gate``) computes in fp32 and gates are the full
      softmax renormalized over the selected top-k (HF's
      ``routing_weights /= routing_weights.sum``) — exactly what
      ``topk_dispatch`` produces;
    - HF processes every routed token; this block keeps the framework's
      static expert capacity (GShard semantics), so over-capacity tokens
      ride the residual during training — at parity-test capacity
      (factor >= E/k) the two are numerically identical;
    - slot priority is always position-major (``causal=True``): this is
      a causal-LM family, and round-major priority leaks future-token
      information through the capacity drop pattern (see
      ``MoeFeedForward`` docstring).

    No dropout (the Llama family has none). The Switch aux loss sows
    into ``losses`` like the encoder MoE.
    """

    config: object  # LlamaConfig (annotated loosely to avoid a cycle)

    @nn.compact
    def __call__(self, hidden, deterministic: bool = True):
        from huggingface_sagemaker_tensorflow_distributed_tpu.parallel.mesh import (
            AXIS_EXPERT,
        )

        cfg = self.config
        E = cfg.num_experts
        _, _, H = hidden.shape
        F = cfg.intermediate_size

        expert_in, combine, non_expert_axes = _route_and_dispatch(
            self, hidden, cfg, causal=True)

        init = nn.initializers.normal(cfg.initializer_range)
        w1 = self.param("w1", init, (E, H, F), cfg.param_dtype)    # gate
        w3 = self.param("w3", init, (E, H, F), cfg.param_dtype)    # up
        w2 = self.param("w2", init, (E, F, H), cfg.param_dtype)    # down
        act = ACT2FN[cfg.hidden_act]
        g = act(jnp.einsum("ebch,ehf->ebcf", expert_in, w1.astype(cfg.dtype)))
        u = jnp.einsum("ebch,ehf->ebcf", expert_in, w3.astype(cfg.dtype))
        out = jnp.einsum("ebcf,efh->ebch", g * u, w2.astype(cfg.dtype))
        out = _constrain(out, AXIS_EXPERT, non_expert_axes)

        return jnp.einsum("bsec,ebch->bsh", combine.astype(cfg.dtype), out)


def group_limited_gate(probs, n_group: int, topk_group: int, top_k: int,
                       scale: float):
    """DeepSeek-V2's ``group_limited_greedy`` gate over float32 ``probs``
    [T, E] (the softmax over ALL experts): the experts are ``n_group``
    groups of ``E / n_group``, a group scores by its best expert, the
    best ``topk_group`` groups are kept, the others' probabilities are
    zeroed, and the ``top_k`` largest of what is left are the token's
    experts (ties go to the lower index, ``lax.top_k``). Returns
    ``(ids [T, top_k] int32, weights [T, top_k] float32)`` with
    ``weights = scale * probs[ids]`` and no renormalisation
    (``norm_topk_prob`` false, ``routed_scaling_factor``)."""
    T, E = probs.shape
    groups = probs.reshape(T, n_group, E // n_group)
    _, kept = lax.top_k(groups.max(axis=-1), topk_group)       # [T, g]
    keep = jnp.sum(jax.nn.one_hot(kept, n_group, dtype=jnp.int32),
                   axis=1) > 0                                 # [T, n_group]
    masked = jnp.where(keep[:, :, None], groups, 0.0).reshape(T, E)
    weights, ids = lax.top_k(masked, top_k)
    return ids.astype(jnp.int32), weights * scale


def sigmoid_bias_gate(scores, bias, top_k: int, scale: float):
    """The ``noaux_tc`` gate over float32 ``scores`` [T, E] (the SIGMOID
    of the router's logits, each expert on its own scale) with one group:
    a token's experts are the ``top_k`` largest of ``scores + bias``
    (``bias`` [E], the load-balancing correction: it selects and never
    weighs; ties go to the lower index, ``lax.top_k``), and their weights
    are the chosen SCORES renormalised to sum to ``scale``
    (``norm_topk_prob``, ``routed_scaling_factor``): ``w_i = scale * s_i /
    (sum of the chosen s + 1e-20)``. Returns ``(ids [T, top_k] int32,
    weights [T, top_k] float32)``, as :func:`group_limited_gate` does."""
    _, ids = lax.top_k(scores + bias[None, :], top_k)
    chosen = jnp.take_along_axis(scores, ids, axis=1)
    weights = chosen / (chosen.sum(axis=-1, keepdims=True) + 1e-20) * scale
    return ids.astype(jnp.int32), weights


def dropless_experts(x, ids, weights, w_gate, w_up, w_down,
                     first_expert: int, act, token_mask=None):
    """The held experts' part of a routed SwiGLU layer, nothing dropped.

    ``x`` [T, H]; ``ids``/``weights`` [T, k] from the gate, ids over ALL
    experts; ``w_gate``/``w_up`` [E, H, F] and ``w_down`` [E, F, H] are
    the ``E`` experts held here, experts ``first_expert ..
    first_expert + E - 1`` of the model. The ``T * k`` (token, expert)
    pairs are sorted by expert, pairs of experts that live elsewhere to a
    tail that no group covers (static shapes: ``T * k`` rows whatever the
    routing), and three grouped matmuls (``lax.ragged_dot``, group =
    expert) run over the sorted rows. Returns ``(y [T, H], counts [E]
    int32)``: ``y = sum_{k: id held} weight * Expert_id(x)``, and
    ``counts`` the pairs each held expert got from the tokens that
    ``token_mask`` [T] marks real (all of them without a mask; the pad
    rows of a dispatch are computed like any other and counted out)."""
    T, k = ids.shape
    E = w_gate.shape[0]
    local = ids - first_expert
    held = (local >= 0) & (local < E)
    key = jnp.where(held, local, E).reshape(-1)                # [T*k]
    order = jnp.argsort(key, stable=True)
    onehot = key[:, None] == jnp.arange(E, dtype=key.dtype)[None, :]
    sizes = jnp.sum(onehot, axis=0, dtype=jnp.int32)           # [E]
    xs = x[order // k]                                         # [T*k, H]
    h = act(lax.ragged_dot(xs, w_gate, sizes)) * lax.ragged_dot(
        xs, w_up, sizes)
    ys = lax.ragged_dot(h, w_down, sizes)                      # [T*k, H]
    # back to (token, choice) order; a row of the tail belongs to an
    # expert held elsewhere and whatever it holds is left out
    inverse = jnp.argsort(order)
    ys = ys[inverse].reshape(T, k, -1)
    w = jnp.where(held, weights, 0.0)
    y = jnp.sum(jnp.where(held[:, :, None], ys.astype(jnp.float32), 0.0)
                * w[:, :, None], axis=1).astype(x.dtype)
    if token_mask is None:
        return y, sizes
    real = jnp.repeat(token_mask.reshape(-1), k)[:, None]
    return y, jnp.sum(onehot & real, axis=0, dtype=jnp.int32)
