"""DeepSeek-V2: multi-head LATENT attention (MLA) + shared and routed
SwiGLU experts with a group-limited gate.

What differs from the Llama layout (``models/llama.py``, whose
``LlamaRMSNorm`` / ``LlamaMlp`` / ``rope_tables`` this family reuses):

- **Latent attention.** Keys and values of all heads are functions of ONE
  compressed row per token: ``c = RMSNorm(x W_kva[:, :r])`` (``r`` =
  ``kv_lora_rank``) and one rotary key ``k_pe = rope(x W_kva[:, r:])``
  shared by every head. The decode cache holds exactly that row, ``c |
  k_pe`` (``r + qk_rope_head_dim`` values a token a layer, no heads axis),
  as the cache variable ``cached_latent`` ``[B, 1, max_len, width]``,
  ``width`` the row rounded up to the TPU's 128 lanes with zeros
  (:func:`latent_width`: 640 for the published 576. The compiler stores a
  576-wide minor dim as 640 lanes anyway, or else lays the pool out with
  its BLOCK axis minor and re-lays the whole pool out twice a layer a
  step: ten 400 MB copies in a decode step's program, rehearsal compile
  for the v5e, PR 28).
  Two forms of the same function attend it, chosen by the SHAPE of the
  call (:func:`latent_path`) and by nothing a user sets:

  * *absorbed* (one query a row, the decode step): ``W_kvb``'s key half
    is folded into the query (``q_nope W_uk^T``) and its value half is
    applied after the weighted sum, so attention runs in the latent
    space and the cache is never expanded. Over a contiguous cache
    (``generate_causal``, the serving engine's gather step) that is
    :func:`attend_absorbed`; where the cache scope holds a
    ``block_tables`` sibling (the engine's paged step, its choice on a
    TPU) the variable ``cached_latent`` is the layer's POOL
    ``[num_blocks, block_size, width]``: the step's row is written into
    it in place and ONE fused kernel attends the pages each row holds
    (``ops/pallas_paged_latent_attention.py``), with no copy of the
    cache. A layer-call of 32 slots at doc-sat's contexts (2,860 a
    slot in the 8,192 bucket) is 0.34 ms by the kernel (38% of the
    MXU's peak) where the gather's four operations were 3.5 ms of the
    step's program (my chip runs, PR 34, and the ledger's PR 33 line;
    PERF.md 6);
  * *expanded* (a chunk of queries, prefill and the plain forward): the
    latent rows are expanded to per-head ``k_nope | v`` through
    ``W_kvb``, a block of keys at a time under a running softmax, so that
    neither the expanded keys nor the scores of a long bucket ever exist
    whole, and the blocks of a bucket past the context are skipped. That
    walk has two forms of its own (:func:`expanded_form`, again a pure
    function of what the code sees): on a TPU, outside a mesh, at shapes
    that tile (the serving engine's three prefill programs) ONE fused
    kernel in which a block's scores never leave the chip
    (``ops/pallas_latent_attention.py``, :func:`attend_expanded_kernel`);
    everywhere else (a CPU, ``model.init``'s 8 tokens, a ragged plain
    forward, the Trainer's mesh) the XLA loop (:func:`attend_expanded`),
    which is also the kernel's reference in the tests. A
    four-row layer-call of 512 queries at start 4,096 is 17.1 ms by the
    kernel (41% of the MXU's peak) against 46.7 ms by the loop (15%:
    each block's ``f32[4,128,512,512]`` scores go to HBM and back), at
    start 7,680 26.7 against 81.9, and where the rows' contexts differ
    (512 to 7,680) 15.3 against 81.9, the kernel skipping a row at a
    time (my chip runs, PR 32; PERF.md 6).

- **RoPE** rotates ``q_pe`` and ``k_pe`` only. The published model
  rotates adjacent pairs ``(2i, 2i+1)``; this code does what HF's port
  does: it permutes the rotary dims to the half-split order and applies
  the rotate-half form (``models/llama.py::apply_rope``). The same
  rotation, and the permutation is the same on ``q_pe`` and ``k_pe``, so
  every score is the published one. (The benchmark's reference rotates
  adjacent pairs.) Frequencies are YaRN's (``_scaled_inv_freq``), and
  the softmax scale carries ``mscale_all_dim``'s temperature squared.
- **FFN.** The first ``first_k_dense_replace`` layers are a SwiGLU; the
  others add ``n_shared_experts`` fused shared experts to routed experts
  under ``models/moe.py::group_limited_gate`` (float32 softmax over all
  experts, best ``topk_group`` of ``n_group`` groups, top
  ``num_experts_per_tok``, weights times ``routed_scaling_factor``, no
  renormalisation) and ``dropless_experts`` (nothing dropped; a token's
  routing depends on that token alone).
- **The share held here.** ``experts_held`` / ``expert_rank`` say which
  routed experts this program holds (experts ``rank * held ..``): the
  router keeps its published width, the layer computes ``Shared(h)`` plus
  the held experts' part, and that partial result goes on. With all
  experts held it is the uncut model. On one chip the layer runs without
  its exchange; nothing stands in for the absent chips.

Out of scope: the training-only auxiliary losses (``seq_aux``), and
loading a published checkpoint (``models/convert.py`` has no mapping for
this family; ROADMAP).
"""

from __future__ import annotations

import dataclasses
import functools
from dataclasses import dataclass
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from huggingface_sagemaker_tensorflow_distributed_tpu.models.layers import ACT2FN
from huggingface_sagemaker_tensorflow_distributed_tpu.models.llama import (
    LlamaMlp,
    LlamaRMSNorm,
    _dense,
    apply_rope,
    rope_tables,
    yarn_mscale,
)
from huggingface_sagemaker_tensorflow_distributed_tpu.models.moe import (
    dropless_experts,
    group_limited_gate,
)
from huggingface_sagemaker_tensorflow_distributed_tpu.ops import (
    pallas_latent_attention,
)
from huggingface_sagemaker_tensorflow_distributed_tpu.ops.attention import (
    scatter_paged_kv,
)
from huggingface_sagemaker_tensorflow_distributed_tpu.ops.pallas_paged_latent_attention import (
    paged_latent_decode_attention,
)
from huggingface_sagemaker_tensorflow_distributed_tpu.parallel.mesh import (
    maybe_current_mesh,
)

NEG_INF = -1e9
# keys a chunk of queries attends at a time: the XLA loop's block
# (attend_expanded) and the fused kernel's query and key block
KEY_BLOCK = 512
# the collection the routed layers sow their per-expert pair counts into
MOE_STATS = "moe_stats"


def latent_width(cfg) -> int:
    """Values of a cached latent row as stored: ``kv_lora_rank +
    qk_rope_head_dim`` rounded up to a multiple of 128 lanes."""
    return -(-(cfg.kv_lora_rank + cfg.qk_rope_head_dim) // 128) * 128


def latent_path(q_len: int) -> str:
    """``absorbed`` | ``expanded``: the form a call with ``q_len`` queries
    a row attends by. A chunk attends expanded: measured on the v5e at
    the 8,192 bucket, that form was the faster on every dispatch
    (PERF.md 6, PR 28). HOW an expanded call walks its keys is
    :func:`expanded_form`'s answer."""
    return "absorbed" if q_len == 1 else "expanded"


EXPANDED_FORMS = ("kernel", "xla_loop")


def expanded_form(cfg, q_len: int, width: int, *, platform: str,
                  mesh: bool) -> str:
    """``kernel`` | ``xla_loop``: how an expanded call of ``q_len``
    queries a row over ``width`` keys attends. A pure function of what
    the code can see, and nothing a user sets: the fused kernel
    (``ops/pallas_latent_attention.py``) on a TPU, outside a mesh (a
    Mosaic kernel is not partitioned by GSPMD, and the Trainer's steps
    always trace under one), for shapes it has blocks for (queries and
    keys whole multiples of ``KEY_BLOCK``, the widths whole lane tiles,
    bf16 or float32: ``pallas_latent_attention.takes``); the XLA loop
    (:func:`attend_expanded`) for everything else: a CPU, the 8-token
    ``model.init``, a ragged plain forward."""
    fits = pallas_latent_attention.takes(
        q_len=q_len, width=width, block=KEY_BLOCK, rank=cfg.kv_lora_rank,
        nope=cfg.qk_nope_head_dim, v_dim=cfg.v_head_dim,
        row=latent_width(cfg), dtype=cfg.dtype)
    return "kernel" if platform == "tpu" and not mesh and fits \
        else "xla_loop"


def _seen_form(cfg, q_len: int, width: int) -> str:
    """:func:`expanded_form` of what this process sees: the default
    backend's platform and whether an ambient mesh is set."""
    return expanded_form(cfg, q_len, width, platform=jax.default_backend(),
                         mesh=maybe_current_mesh() is not None)


@dataclass(frozen=True)
class DeepseekV2Config:
    vocab_size: int = 102400
    hidden_size: int = 5120
    num_layers: int = 60                   # num_hidden_layers
    num_heads: int = 128                   # num_attention_heads
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    intermediate_size: int = 12288         # the dense layers' SwiGLU
    moe_intermediate_size: int = 1536      # one expert's
    n_routed_experts: int = 160            # the router's width
    n_shared_experts: int = 2
    num_experts_per_tok: int = 6
    n_group: int = 8
    topk_group: int = 3
    routed_scaling_factor: float = 16.0
    first_k_dense_replace: int = 1
    # the share of the routed experts held here: experts
    # expert_rank * experts_held .. + experts_held - 1 (None: all)
    experts_held: Optional[int] = None
    expert_rank: int = 0
    max_position_embeddings: int = 163840
    rope_theta: float = 10000.0
    rope_scaling: Optional[tuple] = None   # sorted items of the HF dict
    rms_norm_eps: float = 1e-6
    hidden_act: str = "silu"
    initializer_range: float = 0.02
    tie_word_embeddings: bool = False
    bos_token_id: int = 100000
    eos_token_id: int = 100001
    pad_token_id: int = 100001
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32
    rms_unit_offset: bool = False          # read by LlamaRMSNorm
    model_type: str = "deepseek_v2"

    def __post_init__(self):
        held = self.held
        if self.tie_word_embeddings:
            raise ValueError("deepseek_v2 has an untied head; "
                             "tie_word_embeddings is not implemented")
        if self.n_routed_experts % self.n_group:
            raise ValueError(
                f"n_routed_experts {self.n_routed_experts} is not a "
                f"multiple of n_group {self.n_group}")
        if held < 1 or self.n_routed_experts % held:
            raise ValueError(
                f"experts_held {held} must divide n_routed_experts "
                f"{self.n_routed_experts}")
        if not 0 <= self.expert_rank < self.n_routed_experts // held:
            raise ValueError(
                f"expert_rank {self.expert_rank} outside the "
                f"{self.n_routed_experts // held} shares of {held} experts")

    @property
    def held(self) -> int:
        return (self.n_routed_experts if self.experts_held is None
                else self.experts_held)

    @property
    def rope_scaling_dict(self) -> Optional[dict]:
        return dict(self.rope_scaling) if self.rope_scaling else None

    @property
    def num_moe_layers(self) -> int:
        return max(self.num_layers - self.first_k_dense_replace, 0)

    @property
    def softmax_scale(self) -> float:
        """``(nope + rope)^-0.5 * m^2``, ``m`` YaRN's temperature over
        all dims (``mscale_all_dim``): 0.11472 as published."""
        scale = (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5
        s = self.rope_scaling_dict
        if s:
            m = yarn_mscale(s["factor"], s.get("mscale_all_dim", 0))
            scale *= m * m
        return scale

    @property
    def rope_factor(self) -> float:
        """What cos and sin are multiplied by: ``mscale(factor, mscale) /
        mscale(factor, mscale_all_dim)``, 1 as published."""
        s = self.rope_scaling_dict
        if not s:
            return 1.0
        return (yarn_mscale(s["factor"], s.get("mscale", 1))
                / yarn_mscale(s["factor"], s.get("mscale_all_dim", 0)))


def latent_moe_config_kw(hf_config: dict, family: str) -> dict:
    """The keyword arguments of a :class:`DeepseekV2Config` (or of a
    subclass: ``models/xing4.py``) that an HF ``config.json`` mapping
    gives whichever gate the family has: widths, depth, YaRN, the share
    held here, ids. Raises on what latent attention here does not
    compute."""
    scaling = hf_config.get("rope_scaling")
    rope_scaling = None
    if scaling:
        rope_type = scaling.get("rope_type", scaling.get("type"))
        if rope_type != "yarn":
            raise ValueError(
                f"rope_scaling type {rope_type!r} is not implemented for "
                f"{family} (yarn only): {scaling!r}")
        missing = [k for k in ("factor", "original_max_position_embeddings")
                   if k not in scaling]
        if missing:
            raise ValueError(f"yarn rope_scaling is missing {missing}: "
                             f"{scaling!r}")
        rope_scaling = tuple(sorted(scaling.items()))
    if hf_config.get("q_lora_rank") is None:
        raise ValueError(f"{family} without q_lora_rank (the Lite "
                         "layout: an uncompressed query) is not implemented")
    return dict(
        vocab_size=hf_config["vocab_size"],
        hidden_size=hf_config["hidden_size"],
        num_layers=hf_config["num_hidden_layers"],
        num_heads=hf_config["num_attention_heads"],
        q_lora_rank=hf_config["q_lora_rank"],
        kv_lora_rank=hf_config["kv_lora_rank"],
        qk_nope_head_dim=hf_config["qk_nope_head_dim"],
        qk_rope_head_dim=hf_config["qk_rope_head_dim"],
        v_head_dim=hf_config["v_head_dim"],
        intermediate_size=hf_config["intermediate_size"],
        moe_intermediate_size=hf_config["moe_intermediate_size"],
        n_routed_experts=hf_config["n_routed_experts"],
        n_shared_experts=hf_config.get("n_shared_experts", 0),
        num_experts_per_tok=hf_config["num_experts_per_tok"],
        n_group=hf_config["n_group"],
        topk_group=hf_config["topk_group"],
        routed_scaling_factor=float(hf_config.get("routed_scaling_factor",
                                                  1.0)),
        first_k_dense_replace=hf_config.get("first_k_dense_replace", 0),
        experts_held=hf_config.get("experts_held"),
        expert_rank=hf_config.get("expert_rank", 0),
        max_position_embeddings=hf_config.get("max_position_embeddings",
                                              2048),
        rope_theta=float(hf_config.get("rope_theta", 10000.0)),
        rope_scaling=rope_scaling,
        rms_norm_eps=hf_config.get("rms_norm_eps", 1e-6),
        hidden_act=hf_config.get("hidden_act", "silu"),
        initializer_range=hf_config.get("initializer_range", 0.02),
        tie_word_embeddings=hf_config.get("tie_word_embeddings", False),
        bos_token_id=hf_config.get("bos_token_id", 100000),
        eos_token_id=hf_config.get("eos_token_id", 100001),
        pad_token_id=(hf_config["pad_token_id"]
                      if hf_config.get("pad_token_id") is not None
                      else hf_config.get("eos_token_id", 100001)),
    )


def refuse_unless(family: str, hf_config: dict, wanted: dict,
                  why: str) -> None:
    """Raise, by key, on a ``config.json`` value other than the one this
    family's modules compute: ``wanted`` maps a key to ``(the value run,
    what an absent key means)``."""
    for key, (want, default) in wanted.items():
        got = hf_config.get(key, default)
        if got != want:
            raise ValueError(f"{family} {key}={got!r} is not implemented "
                             f"(only {want!r}): {why}")


def deepseek_v2_config_from_hf(hf_config: dict, **overrides) -> DeepseekV2Config:
    """The program's configuration from an HF ``config.json`` mapping.
    ``experts_held`` / ``expert_rank`` (not HF keys) may ride in the
    mapping or in ``overrides``. Raises on what the modules do not
    compute rather than load and diverge."""
    refuse_unless("deepseek_v2", hf_config, {
        "scoring_func": ("softmax", "softmax"),
        "topk_method": ("group_limited_greedy", "group_limited_greedy"),
        "norm_topk_prob": (False, False),
        "moe_layer_freq": (1, 1),
        "attention_bias": (False, False),
    }, "this family has a softmax, group-limited gate. A sigmoid, "
       "bias-corrected gate with renormalised weights is the xing4_0 "
       "family's (models/xing4.py); sparse expert placement is no "
       "family's here (ROADMAP)")
    kw = latent_moe_config_kw(hf_config, "deepseek_v2")
    kw.update(overrides)
    kw.pop("use_pooler", None)             # encoder-family knob
    return DeepseekV2Config(**kw)


# -- the two forms of latent attention ---------------------------------------


def attend_expanded(q_nope, q_pe, latent, bias, w_kvb, *, rank: int,
                    scale: float, key_block: int = KEY_BLOCK):
    """Latent attention, expanded form. ``q_nope`` [B, S, H, nope],
    ``q_pe`` [B, S, H, rope] (rotated), ``latent`` [B, W, >= rank + rope]
    (``c | k_pe | zeros``), ``bias`` [B, S, W] float32 additive mask,
    ``w_kvb`` [rank, H, nope + v]. Per block of keys: ``k_nope | v = c
    W_kvb``, ``score = (q_nope k_nope + q_pe k_pe) * scale``. Returns
    [B, S, H, v].

    The keys go ``key_block`` at a time under a running softmax (the
    flash recurrence: running maximum ``m``, running sum ``l``, rescaled
    accumulator), so that neither the scores of a long bucket nor the
    keys and values expanded from its rows ever exist whole: [4 rows,
    128 heads, 512 queries, 512 keys] of float32 scores is 0.54 GB where
    8,192 keys at once would be 8.6 GB. (Narrow score rows are also what
    the v5e's compiler reduces well: one softmax over
    ``f32[4,16,512,8192]`` took 100 ms, forty of them 4.15 s a dispatch,
    my chip run, PR 28.) A block no query may see (every key past the
    longest row's context, in a bucket wider than the context) is
    skipped: its terms are exactly zero.

    This is the XLA form: what runs on a CPU, under a mesh and at shapes
    the fused kernel has no blocks for (:func:`expanded_form`), and the
    reference :func:`attend_expanded_kernel` is held to. On the v5e a
    block's scores cost it five passes over HBM, 47 ms a four-row
    layer-call at start 4,096 where the kernel takes 17 (PR 32)."""
    B, W = latent.shape[:2]
    S, H = q_nope.shape[1:3]
    nope, rot = q_nope.shape[-1], q_pe.shape[-1]
    kb = key_block if W > key_block and W % key_block == 0 else W

    def step(i, carry):
        rows = lax.dynamic_slice_in_dim(latent, i * kb, kb, axis=1)
        bias_k = lax.dynamic_slice_in_dim(bias, i * kb, kb, axis=2)

        def attend(carry):
            m, l, acc = carry
            kv = jnp.einsum("bwr,rhd->bwhd", rows[..., :rank], w_kvb)
            scores = (jnp.einsum("bshd,bwhd->bhsw", q_nope, kv[..., :nope],
                                 preferred_element_type=jnp.float32)
                      + jnp.einsum("bshd,bwd->bhsw", q_pe,
                                   rows[..., rank:rank + rot],
                                   preferred_element_type=jnp.float32))
            scores = scores * scale + bias_k[:, None]
            m_new = jnp.maximum(m, scores.max(axis=-1))
            p = jnp.exp(scores - m_new[..., None])
            keep = jnp.exp(m - m_new)
            l = l * keep + p.sum(axis=-1)
            acc = (acc * keep.transpose(0, 2, 1)[..., None]
                   + jnp.einsum("bhsw,bwhd->bshd", p.astype(latent.dtype),
                                kv[..., nope:]).astype(jnp.float32))
            return m_new, l, acc

        return lax.cond(jnp.any(bias_k > NEG_INF / 2), attend,
                        lambda c: c, carry)

    m, l, acc = lax.fori_loop(0, W // kb, step, (
        jnp.full((B, H, S), -1e30, jnp.float32),
        jnp.zeros((B, H, S), jnp.float32),
        jnp.zeros((B, S, H, w_kvb.shape[-1] - nope), jnp.float32)))
    return (acc / l.transpose(0, 2, 1)[..., None]).astype(latent.dtype)


def mask_bias(start, q_len: int, key_valid, width: int):
    """The additive float32 mask ``[B, q_len, width]`` of a step: key
    ``j`` is seen by query ``s`` of row ``b`` iff ``j <= start[b] + s``
    and ``key_valid[b, j]`` (None: every key)."""
    q_slot = start[:, None] + jnp.arange(q_len)[None, :]
    seen = jnp.arange(width)[None, None, :] <= q_slot[:, :, None]
    if key_valid is not None:
        seen = seen & key_valid[:, None, :]
    return jnp.where(seen, 0.0, NEG_INF).astype(jnp.float32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def attend_expanded_kernel(q_nope, q_pe, latent, w_kvb, start, key_valid,
                           rank: int, scale: float, block: int):
    """:func:`attend_expanded` through the fused kernel
    (``ops/pallas_latent_attention.py``): the same function of the same
    operands, with the mask given as what it is made from (``start``
    [B], ``key_valid`` [B, W] or None: :func:`mask_bias`) so that no
    ``[B, S, W]`` bias is built, and no score leaves the chip. The kernel
    has no backward pass of its own: a gradient recomputes through the
    XLA form."""
    return pallas_latent_attention.latent_prefill_attention(
        q_nope, q_pe, latent, w_kvb, start, key_valid, rank=rank,
        scale=scale, block=block)


def _kernel_fwd(q_nope, q_pe, latent, w_kvb, start, key_valid, rank, scale,
                block):
    out = attend_expanded_kernel(q_nope, q_pe, latent, w_kvb, start,
                                 key_valid, rank, scale, block)
    return out, (q_nope, q_pe, latent, w_kvb, start, key_valid)


def _kernel_bwd(rank, scale, block, res, g):
    q_nope, q_pe, latent, w_kvb, start, key_valid = res
    bias = mask_bias(start, q_nope.shape[1], key_valid, latent.shape[1])
    _, vjp = jax.vjp(
        lambda qn, qp, lat, w: attend_expanded(
            qn, qp, lat, bias, w, rank=rank, scale=scale, key_block=block),
        q_nope, q_pe, latent, w_kvb)
    return (*vjp(g), None, None)


attend_expanded_kernel.defvjp(_kernel_fwd, _kernel_bwd)


def absorbed_query(q_nope, q_pe, w_kvb, width: int, dtype):
    """The query of the absorbed form, ``q_nope W_uk^T | q_pe`` padded
    with zeros to the cached row's ``width``: [B, 1, H, width] in the
    cache's ``dtype``, so that a score is one dot product with the row
    as stored."""
    nope = q_nope.shape[-1]
    q_lat = jnp.einsum("bshd,rhd->bshr", q_nope, w_kvb[..., :nope])
    q_cat = jnp.concatenate([q_lat.astype(dtype), q_pe], axis=-1)
    return jnp.pad(q_cat, [(0, 0)] * 3 + [
        (0, width - q_cat.shape[-1])])                  # the row's zeros


def absorbed_values(o_lat, w_kvb, nope: int):
    """``W_uv`` after the weighted sum: ``o_lat`` [B, 1, H, rank] in the
    latent space to [B, 1, H, v]."""
    return jnp.einsum("bshr,rhd->bshd", o_lat, w_kvb[..., nope:])


def attend_absorbed(q_nope, q_pe, latent, bias, w_kvb, *, rank: int,
                    scale: float):
    """Latent attention, absorbed form, for ONE query a row (the decode
    step): the same function as :func:`attend_expanded` with ``W_kvb``'s
    key half folded into the query and its value half applied after the
    weighted sum, so the scores and the sum run against the latent rows
    themselves: ``score = (q_nope W_uk^T | q_pe) . (c | k_pe) * scale``,
    ``out = (softmax(score) c) W_uv``. (For a chunk of queries it
    measured slower than the expanded form on every dispatch: PERF.md
    6, PR 28.) This is the form over a GATHERED cache ``latent`` [B, W,
    width]; over the pages of a pool the middle of it is one fused
    kernel (``DeepseekV2Attention``'s paged branch)."""
    assert q_nope.shape[1] == 1, "absorbed attention takes one query a row"
    q_cat = absorbed_query(q_nope, q_pe, w_kvb, latent.shape[-1],
                           latent.dtype)
    scores = jnp.einsum("bshc,bwc->bhsw", q_cat, latent,
                        preferred_element_type=jnp.float32) * scale
    p = jax.nn.softmax(scores + bias[:, None], axis=-1).astype(latent.dtype)
    o_lat = jnp.einsum("bhsw,bwr->bshr", p, latent[..., :rank])
    return absorbed_values(o_lat.astype(latent.dtype), w_kvb,
                           q_nope.shape[-1])


def _to_half_split(x):
    """Rotary dims from the published adjacent-pair order to the
    half-split order ``apply_rope`` rotates (HF's port does the same)."""
    return jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)


class DeepseekV2Attention(nn.Module):
    config: DeepseekV2Config

    @nn.compact
    def __call__(self, hidden, key_valid=None, rope=None,
                 decode: bool = False):
        cfg = self.config
        B, S, _ = hidden.shape
        H, rank = cfg.num_heads, cfg.kv_lora_rank
        nope, rot, vd = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                         cfg.v_head_dim)
        cq = LlamaRMSNorm(cfg, name="q_a_ln")(
            _dense(cfg, cfg.q_lora_rank, "q_a_proj")(hidden))
        q = _dense(cfg, H * (nope + rot), "q_b_proj")(cq)
        q = q.reshape(B, S, H, nope + rot)
        q_nope, q_pe = q[..., :nope], q[..., nope:]
        ckv = _dense(cfg, rank + rot, "kv_a_proj")(hidden)
        c = LlamaRMSNorm(cfg, name="kv_a_ln")(ckv[..., :rank])
        # apply_rope takes [B, heads, S, D]; the one k_pe is one "head"
        q_pe = apply_rope(_to_half_split(q_pe).transpose(0, 2, 1, 3),
                          rope).transpose(0, 2, 1, 3)
        k_pe = apply_rope(_to_half_split(ckv[..., rank:])[:, None],
                          rope)[:, 0]
        width = latent_width(cfg)
        latent = jnp.concatenate(
            [c, k_pe.astype(c.dtype),
             jnp.zeros((B, S, width - rank - rot), c.dtype)], axis=-1)
        w_kvb = self.param(
            "kv_b_proj", nn.initializers.normal(cfg.initializer_range),
            (rank, H, nope + vd), cfg.param_dtype).astype(cfg.dtype)

        start = jnp.zeros((B,), jnp.int32)      # each row's first query
        if decode:
            is_init = self.has_variable("cache", "cached_latent")
            cached = self.variable("cache", "cached_latent", jnp.zeros,
                                   (B, 1, S, width), latent.dtype)
            cache_index = self.variable(
                "cache", "cache_index", lambda: jnp.zeros((B,), jnp.int32))
            if self.has_variable("cache", "block_tables"):
                # the serving engine's paged decode step: ``cached``
                # holds the layer's POOL [num_blocks, block_size, width]
                # and a block table a row rides beside the write index.
                # The step's row goes into the pool in place, then ONE
                # fused kernel attends the pages each row holds, in the
                # absorbed form: no copy of the cache is assembled
                if S != 1:
                    raise ValueError(
                        "paged decode is single-token (the fused kernel "
                        f"takes one query a row, got q_len {S})")
                tables = self.get_variable("cache", "block_tables")
                cur = cache_index.value                         # [B]
                cached.value = scatter_paged_kv(cached.value, tables, cur,
                                                latent[:, 0])
                cache_index.value = cur + 1
                o_lat = paged_latent_decode_attention(
                    absorbed_query(q_nope, q_pe, w_kvb, width,
                                   latent.dtype)[:, 0],
                    cached.value, tables, cur + 1, rank=rank,
                    scale=cfg.softmax_scale)
                ctx = absorbed_values(o_lat[:, None], w_kvb, nope)
                return _dense(cfg, cfg.hidden_size, "o_proj")(
                    ctx.reshape(B, 1, H * vd))
            if is_init:
                # the write protocol of models/llama.py::write_kv_cache:
                # each row's new latent rows at its own write index
                start = cache_index.value
                buf = jax.vmap(
                    lambda b, new, i: lax.dynamic_update_slice(
                        b, new, (0, i, 0)))(cached.value, latent[:, None],
                                            start)
                cached.value = buf
                cache_index.value = start + S
                latent = buf[:, 0]                          # [B, W, width]
        W = latent.shape[1]
        path = latent_path(S)
        if path == "expanded" and _seen_form(cfg, S, W) == "kernel":
            ctx = attend_expanded_kernel(
                q_nope, q_pe, latent, w_kvb, start, key_valid, rank,
                cfg.softmax_scale, KEY_BLOCK)
        else:
            attend = (attend_absorbed if path == "absorbed"
                      else attend_expanded)
            ctx = attend(q_nope, q_pe, latent,
                         mask_bias(start, S, key_valid, W), w_kvb,
                         rank=rank, scale=cfg.softmax_scale)
        return _dense(cfg, cfg.hidden_size, "o_proj")(
            ctx.reshape(B, S, H * vd))


class DeepseekV2MoE(nn.Module):
    """``Shared(h) + sum over the held experts a token chose``; sows the
    held experts' pair counts into :data:`MOE_STATS`."""

    config: DeepseekV2Config

    def gate(self, logits):
        """``(ids, weights)`` [T, k] of the router's float32 ``logits``
        [T, all experts]: this family's gate (a family with another one
        overrides this, and may declare the gate's own parameters)."""
        cfg = self.config
        return group_limited_gate(
            jax.nn.softmax(logits, axis=-1), cfg.n_group, cfg.topk_group,
            cfg.num_experts_per_tok, cfg.routed_scaling_factor)

    @nn.compact
    def __call__(self, hidden, token_mask=None):
        cfg = self.config
        B, S, Hd = hidden.shape
        E, F = cfg.held, cfg.moe_intermediate_size
        init = nn.initializers.normal(cfg.initializer_range)
        router = self.param("router", init, (Hd, cfg.n_routed_experts),
                            cfg.param_dtype)
        w_gate = self.param("experts_gate_proj", init, (E, Hd, F),
                            cfg.param_dtype)
        w_up = self.param("experts_up_proj", init, (E, Hd, F),
                          cfg.param_dtype)
        w_down = self.param("experts_down_proj", init, (E, F, Hd),
                            cfg.param_dtype)
        x = hidden.reshape(B * S, Hd)
        # the gate in float32, as published: on a TPU a float32 matmul
        # runs in bf16 passes unless told otherwise, and a near-tie
        # between the sixth and seventh expert would then flip
        logits = jnp.einsum("th,he->te", x.astype(jnp.float32),
                            router.astype(jnp.float32),
                            precision=lax.Precision.HIGHEST)
        ids, weights = self.gate(logits)
        y, counts = dropless_experts(
            x, ids, weights, w_gate.astype(cfg.dtype),
            w_up.astype(cfg.dtype), w_down.astype(cfg.dtype),
            cfg.expert_rank * E, ACT2FN[cfg.hidden_act],
            token_mask=token_mask)
        self.sow(MOE_STATS, "expert_counts", counts)
        self.sow(MOE_STATS, "expert_ids", ids.reshape(B, S, -1))
        y = y.reshape(B, S, Hd)
        if cfg.n_shared_experts:
            shared = dataclasses.replace(
                cfg, intermediate_size=cfg.n_shared_experts * F)
            y = y + LlamaMlp(shared, name="shared_experts")(hidden)
        return y


class DeepseekV2Block(nn.Module):
    config: DeepseekV2Config
    layer_index: int = 0

    @nn.compact
    def __call__(self, hidden, key_valid=None, rope=None,
                 decode: bool = False, token_mask=None):
        cfg = self.config
        hidden = hidden + DeepseekV2Attention(cfg, name="self_attn")(
            LlamaRMSNorm(cfg, name="input_ln")(hidden), key_valid, rope,
            decode)
        normed = LlamaRMSNorm(cfg, name="post_attn_ln")(hidden)
        if self.layer_index < cfg.first_k_dense_replace:
            return hidden + LlamaMlp(cfg, name="mlp")(normed)
        return hidden + DeepseekV2MoE(cfg, name="moe")(normed, token_mask)


def embed_and_rope(module: nn.Module, input_ids, attention_mask,
                   position_ids, decode: bool):
    """What a backbone of this layout does before its first block, inside
    ``module``'s compact call: ``(embedded tokens [B, S, C], key_valid
    [B, W] or None, (cos, sin))``. Positions are the caller's, or count on
    from the cache's ``position_index`` (``generate_causal``'s steps)."""
    cfg = module.config
    B, S = input_ids.shape
    if position_ids is None:
        offset = 0
        if decode:
            is_init = module.has_variable("cache", "position_index")
            idx = module.variable("cache", "position_index",
                                  lambda: jnp.array(0, jnp.int32))
            if is_init:
                offset = idx.value
                idx.value = offset + S
        position_ids = jnp.broadcast_to(
            offset + jnp.arange(S)[None, :], (B, S))
    cos, sin = rope_tables(position_ids, cfg.qk_rope_head_dim,
                           cfg.rope_theta, cfg.rope_scaling_dict)
    if cfg.rope_factor != 1.0:
        cos, sin = cos * cfg.rope_factor, sin * cfg.rope_factor
    key_valid = None if attention_mask is None else attention_mask > 0
    x = nn.Embed(
        cfg.vocab_size, cfg.hidden_size,
        embedding_init=nn.initializers.normal(cfg.initializer_range),
        dtype=cfg.dtype, param_dtype=cfg.param_dtype,
        name="embed_tokens")(input_ids)
    return x, key_valid, (cos, sin)


class DeepseekV2Model(nn.Module):
    config: DeepseekV2Config

    @nn.compact
    def __call__(self, input_ids, attention_mask=None, position_ids=None,
                 decode: bool = False, token_mask=None):
        cfg = self.config
        x, key_valid, rope = embed_and_rope(self, input_ids, attention_mask,
                                            position_ids, decode)
        for i in range(cfg.num_layers):
            x = DeepseekV2Block(cfg, layer_index=i, name=f"layers_{i}")(
                x, key_valid, rope, decode, token_mask)
        return LlamaRMSNorm(cfg, name="final_ln")(x)


class DeepseekV2ForCausalLM(nn.Module):
    """Same call signature as ``LlamaForCausalLM`` (so ``generate_causal``
    and the serving engine drive it unchanged), plus ``token_mask``
    [B, S]: which tokens are real, for the routed layers' counts only
    (no output depends on it)."""

    config: DeepseekV2Config

    latent_path = staticmethod(latent_path)
    EXPANDED_FORMS = EXPANDED_FORMS

    def expanded_form(self, q_len: int, width: int) -> str:
        """:func:`expanded_form` for a call of this model in this
        process: what the serving engine writes beside ``latent_path``."""
        return _seen_form(self.config, q_len, width)

    def setup(self):
        cfg = self.config
        self.backbone = DeepseekV2Model(cfg)
        self.lm_head = nn.Dense(
            cfg.vocab_size, use_bias=False, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype,
            kernel_init=nn.initializers.normal(cfg.initializer_range),
            name="lm_head")

    def __call__(self, input_ids, attention_mask=None, token_type_ids=None,
                 position_ids=None, deterministic: bool = True,
                 decode: bool = False, token_mask=None):
        hidden = self.backbone(input_ids, attention_mask, position_ids,
                               decode, token_mask)
        return self.lm_head(hidden).astype(jnp.float32)
