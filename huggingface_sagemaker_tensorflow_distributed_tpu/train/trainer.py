"""The training engine: jitted sharded train/eval steps + epoch loop.

TPU-native replacement for the reference's Keras engine usage
(``model.compile`` / ``model.fit`` / ``model.evaluate``, reference
``scripts/train.py:117-153,168-179``; SURVEY.md D5). Instead of a
framework-internal fit loop with an allreduce-wrapping optimizer
(``hvd.DistributedOptimizer``, ``scripts/train.py:114``) and a weight
broadcast callback (``scripts/train.py:127-134``), distribution is
*ambient*: parameters carry replicated/sharded NamedShardings, batches
are globally sharded over the mesh's data axes, and XLA inserts the
gradient all-reduce (ICI/DCN collectives) because the loss is a global
mean. Broadcast-at-start is subsumed by initializing params once under a
replicated sharding constraint.

Emission contract parity: per-epoch history (loss +
``sparse_categorical_accuracy``), ``train_runtime`` wall clock, and
``train_results.txt`` / ``eval_results.txt`` files exactly as the
reference writes them (``scripts/train.py:154-179``), plus the
samples/sec/chip meter the north-star metric needs (BASELINE.md).
"""

from __future__ import annotations

import contextlib
import functools
import inspect
from typing import Any, Callable, Optional

import flax.struct
import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from huggingface_sagemaker_tensorflow_distributed_tpu.config import TrainConfig
from huggingface_sagemaker_tensorflow_distributed_tpu import obs
from huggingface_sagemaker_tensorflow_distributed_tpu.ops.losses import (
    softmax_cross_entropy_with_integer_labels,
)
from huggingface_sagemaker_tensorflow_distributed_tpu.parallel.mesh import (
    data_parallel_size,
    world_size,
)
from huggingface_sagemaker_tensorflow_distributed_tpu.parallel.sharding import (
    param_shardings,
)
from huggingface_sagemaker_tensorflow_distributed_tpu.train.optim import build_optimizer
from huggingface_sagemaker_tensorflow_distributed_tpu.utils.logging import get_logger
from huggingface_sagemaker_tensorflow_distributed_tpu.utils.results import write_results_file
from huggingface_sagemaker_tensorflow_distributed_tpu.utils.timing import StepMeter, Stopwatch

logger = get_logger(__name__)


def _host_snapshot(tree):
    """Fetch a (possibly cross-process sharded) pytree to host memory —
    the collective allgather runs on EVERY host before any fetch, same
    discipline as models/auto.py::save_pretrained."""
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils

        tree = multihost_utils.process_allgather(tree, tiled=True)
    return jax.device_get(tree)


class TrainState(flax.struct.PyTreeNode):
    step: jnp.ndarray
    params: Any
    opt_state: Any


# ---------------------------------------------------------------------------
# Task losses. Each: (apply_fn, params, batch, rngs, train) ->
#   (loss, dict of metric sums + count) — sums so eval aggregates exactly.
# ---------------------------------------------------------------------------

def _masked_sums(per_example, correct, valid):
    """Shared aggregation: masked loss/correct sums + count (+ mean loss).

    ``valid`` is {0,1} broadcastable to ``per_example`` — padded eval rows
    (and padded tokens) contribute nothing, so metrics average over
    exactly the real examples (cf. reference ``scripts/train.py:98-100``
    which relied on ragged tf.data batches instead).
    """
    valid = valid.astype(jnp.float32)
    count = jnp.sum(valid)
    loss_sum = jnp.sum(per_example.astype(jnp.float32) * valid)
    correct_sum = jnp.sum(correct.astype(jnp.float32) * valid)
    loss = loss_sum / jnp.maximum(count, 1.0)
    return loss, {"loss_sum": loss_sum, "correct": correct_sum, "count": count}


def _packed_kwargs(batch) -> dict:
    """Pass-through of the token-packing columns (``pack_examples``):
    ``segment_ids`` keeps attention block-diagonal per packed example,
    ``position_ids`` restarts positions per example. Only forwarded when
    present, so unpacked batches reach models that never grew the
    kwargs."""
    kw = {}
    if "segment_ids" in batch:
        kw["segment_ids"] = batch["segment_ids"]
    if "position_ids" in batch:
        kw["position_ids"] = batch["position_ids"]
    return kw


def _apply(apply_fn, params, batch, rngs, train):
    return apply_fn({"params": params}, batch["input_ids"],
                    batch["attention_mask"],
                    token_type_ids=batch.get("token_type_ids"),
                    deterministic=not train, rngs=rngs,
                    **_packed_kwargs(batch))


def seq_cls_loss(apply_fn, params, batch, rngs, train: bool):
    """SparseCategoricalCrossentropy(from_logits=True) +
    SparseCategoricalAccuracy parity (reference ``scripts/train.py:118-119``)."""
    logits = _apply(apply_fn, params, batch, rngs, train)
    per_ex = softmax_cross_entropy_with_integer_labels(logits, batch["labels"])
    valid = batch.get("valid", jnp.ones_like(per_ex))
    correct = jnp.argmax(logits, -1) == batch["labels"]
    return _masked_sums(per_ex, correct, valid)


def token_cls_loss(apply_fn, params, batch, rngs, train: bool,
                   with_f1: bool = True):
    """Token-level CE with label masking (labels == -100 ignored, the HF
    convention); covers the CoNLL NER breadth config. Eval sums include
    TOKEN-level micro-F1 components over the non-O classes (class 0 =
    outside). NB: published CoNLL baselines report ENTITY-level
    (seqeval) F1, which is stricter — don't compare the two directly.
    Disabled for tasks that merely share the loss shape (MLM, where
    class 0 is a vocab token, not a tag)."""
    logits = _apply(apply_fn, params, batch, rngs, train)
    labels = batch["labels"]
    token_valid = (labels != -100) & (batch["attention_mask"] > 0)
    if "valid" in batch:
        token_valid = token_valid & (batch["valid"][:, None] > 0)
    safe_labels = jnp.maximum(labels, 0)
    per_tok = softmax_cross_entropy_with_integer_labels(logits, safe_labels)
    pred = jnp.argmax(logits, -1)
    correct = pred == safe_labels
    loss, sums = _masked_sums(per_tok, correct, token_valid)
    if with_f1:
        v = token_valid.astype(jnp.float32)
        sums["f1_tp"] = jnp.sum(((pred != 0) & correct).astype(jnp.float32) * v)
        sums["f1_fp"] = jnp.sum(((pred != 0) & ~correct).astype(jnp.float32) * v)
        sums["f1_fn"] = jnp.sum(((safe_labels != 0) & ~correct).astype(jnp.float32) * v)
    return loss, sums


def qa_loss(apply_fn, params, batch, rngs, train: bool):
    """SQuAD span loss: mean of start & end CE (HF parity)."""
    start_logits, end_logits = _apply(apply_fn, params, batch, rngs, train)
    valid = batch.get("valid", jnp.ones(start_logits.shape[0]))
    s_ce = softmax_cross_entropy_with_integer_labels(start_logits, batch["start_positions"])
    e_ce = softmax_cross_entropy_with_integer_labels(end_logits, batch["end_positions"])
    # cast before adding: bool + bool is logical OR, not arithmetic
    s_ok = (jnp.argmax(start_logits, -1) == batch["start_positions"]).astype(jnp.float32)
    e_ok = (jnp.argmax(end_logits, -1) == batch["end_positions"]).astype(jnp.float32)
    return _masked_sums(0.5 * (s_ce + e_ce), 0.5 * (s_ok + e_ok), valid)


def seq2seq_loss(apply_fn, params, batch, rngs, train: bool,
                 epsilon: float = 0.0):
    """Teacher-forced LM cross-entropy over non-pad target tokens
    (labels == -100 ignored, HF convention); covers the T5/CNN-DM
    breadth config. Metric is next-token accuracy.

    ``epsilon`` > 0 adds uniform label smoothing at TRAIN time (T5/BART
    fine-tuning convention, HF ``--label_smoothing_factor``):
    q = (1-eps)*onehot + eps/V decomposes into
    (1-eps)*CE + eps*(logsumexp - mean(logits)) — computed from the
    logits directly, no [*, V] one-hot ever materialized. Eval keeps
    the plain CE so eval_loss stays comparable across settings."""
    logits = apply_fn({"params": params}, batch["input_ids"],
                      batch["attention_mask"], batch["decoder_input_ids"],
                      batch.get("decoder_attention_mask"),
                      deterministic=not train, rngs=rngs)
    labels = batch["labels"]
    token_valid = labels != -100
    if "valid" in batch:
        token_valid = token_valid & (batch["valid"][:, None] > 0)
    safe_labels = jnp.maximum(labels, 0)
    per_tok = softmax_cross_entropy_with_integer_labels(logits, safe_labels)
    if epsilon > 0 and train:
        lse = jax.scipy.special.logsumexp(
            logits.astype(jnp.float32), axis=-1)
        uniform = lse - jnp.mean(logits.astype(jnp.float32), axis=-1)
        per_tok = (1.0 - epsilon) * per_tok + epsilon * uniform
    correct = jnp.argmax(logits, -1) == safe_labels
    return _masked_sums(per_tok, correct, token_valid)


def make_smoothed_seq2seq_loss(epsilon: float):
    return functools.partial(seq2seq_loss, epsilon=epsilon)


def causal_lm_loss(apply_fn, params, batch, rngs, train: bool):
    """Next-token CE for decoder-only LMs (GPT-2 family): logits at
    position i predict token i+1; pad targets (and padded eval rows)
    are masked out. Metric is next-token accuracy."""
    logits = _apply(apply_fn, params, batch, rngs, train)        # [B,S,V]
    labels = batch["labels"][:, 1:]
    logits = logits[:, :-1]
    token_valid = (batch["attention_mask"][:, 1:] > 0) & (labels != -100)
    if "valid" in batch:
        token_valid = token_valid & (batch["valid"][:, None] > 0)
    safe_labels = jnp.maximum(labels, 0)
    per_tok = softmax_cross_entropy_with_integer_labels(logits, safe_labels)
    correct = jnp.argmax(logits, -1) == safe_labels
    return _masked_sums(per_tok, correct, token_valid)


def rtd_loss(apply_fn, params, batch, rngs, train: bool):
    """Replaced-token detection (ELECTRA pretraining): per-token binary
    CE on whether the token was substituted; -100/pad positions are
    ignored. Metric is detection accuracy."""
    logits = _apply(apply_fn, params, batch, rngs, train)        # [B,S]
    labels = batch["labels"]
    token_valid = (labels != -100) & (batch["attention_mask"] > 0)
    if "valid" in batch:
        token_valid = token_valid & (batch["valid"][:, None] > 0)
    target = jnp.maximum(labels, 0).astype(jnp.float32)
    per_tok = optax.sigmoid_binary_cross_entropy(
        logits.astype(jnp.float32), target)
    correct = (logits > 0) == (target > 0.5)
    return _masked_sums(per_tok, correct, token_valid)


def _make_sharded_fused_ce(block_n: int, block_v: int,
                           interpret: bool | None,
                           label_smoothing: float = 0.0):
    """The shard_mapped blocked-vocab CE call the fused losses share:
    ``ce(hidden [B,T,H], weight [V,H], labels [B,T]) → (per_tok, pred)``,
    per-dp-shard through the Pallas kernel, weight cotangent psummed by
    the shard_map transpose."""
    from jax.sharding import PartitionSpec as P

    from huggingface_sagemaker_tensorflow_distributed_tpu.ops.pallas_vocab_ce import (
        fused_vocab_cross_entropy,
    )
    from huggingface_sagemaker_tensorflow_distributed_tpu.parallel.mesh import (
        data_axis_names,
        maybe_current_mesh,
    )

    def ce(h, w, lab):
        n = h.shape[0] * h.shape[1]
        per_tok, pred = fused_vocab_cross_entropy(
            h.reshape(n, h.shape[2]), w, lab.reshape(n),
            block_n=block_n, block_v=block_v, interpret=interpret,
            label_smoothing=label_smoothing)
        return per_tok.reshape(lab.shape), pred.reshape(lab.shape)

    mesh = maybe_current_mesh()
    batch_axes = data_axis_names()
    if mesh is not None and any(
            mesh.shape.get(a, 1) > 1 for a in batch_axes):
        # check_vma=False: pallas_call does not annotate varying-mesh
        # axes on its outputs, which the default vma check rejects
        ce = jax.shard_map(ce, mesh=mesh,
                           in_specs=(P(batch_axes), P(), P(batch_axes)),
                           out_specs=(P(batch_axes), P(batch_axes)),
                           check_vma=False)
    return ce


def make_fused_causal_lm_loss(model, block_n: int = 256, block_v: int = 512,
                              interpret: bool | None = None):
    """``causal_lm_loss`` without the [B, S, V] logits: the model exposes
    ``hidden_and_embedding`` and the blocked-vocab Pallas kernel
    (``ops/pallas_vocab_ce.py``) reduces head-matmul + CE + argmax on
    chip. The kernel is shard_mapped over the data axes, so each dp
    shard computes its own tokens and the weight cotangent is psummed by
    the shard_map transpose (the same all-reduce the unfused head matmul
    would produce). Instead of slicing off the last position (which
    would break the token-block tiling: S-1 is odd), labels are shifted
    left with a -100 pad so every position is computed and the last is
    masked — identical masked sums to ``causal_lm_loss``."""

    def loss(apply_fn, params, batch, rngs, train: bool):
        # the PASSED apply_fn, not model.apply: the Trainer wraps it to
        # collect sown MoE aux losses (mutable=["losses"]) — calling the
        # model directly would silently drop router load balancing
        hidden, embedding = apply_fn(
            {"params": params}, batch["input_ids"], batch["attention_mask"],
            deterministic=not train, rngs=rngs,
            method=model.hidden_and_embedding,
            **_packed_kwargs(batch))                         # [B,S,H], [V,H]
        B = hidden.shape[0]
        labels = batch["labels"]
        shifted = jnp.concatenate(
            [labels[:, 1:], jnp.full((B, 1), -100, labels.dtype)], axis=1)
        token_valid = jnp.concatenate(
            [(batch["attention_mask"][:, 1:] > 0) & (labels[:, 1:] != -100),
             jnp.zeros((B, 1), bool)], axis=1)
        if "valid" in batch:
            token_valid = token_valid & (batch["valid"][:, None] > 0)
        safe_labels = jnp.maximum(shifted, 0)
        ce = _make_sharded_fused_ce(block_n, block_v, interpret)
        per_tok, pred = ce(hidden, embedding, safe_labels)
        correct = pred == safe_labels
        return _masked_sums(per_tok, correct, token_valid)

    return loss


def make_fused_seq2seq_loss(model, block_n: int = 256, block_v: int = 512,
                            interpret: bool | None = None,
                            label_smoothing: float = 0.0):
    """``seq2seq_loss`` without the [B, T, V] logits: the encoder-decoder
    model exposes ``seq2seq_hidden_and_embedding`` (pre-head decoder
    hidden + LM weight — T5 tied/untied and BART) and the blocked-vocab
    Pallas kernel computes CE + argmax on chip, shard_mapped per dp
    shard like the causal path. No label shifting: seq2seq labels align
    with decoder positions (teacher forcing is in decoder_input_ids).
    ``label_smoothing`` rides into the kernel as a static epsilon (a
    running logit-sum joins the online-softmax stats) at TRAIN time;
    eval uses the plain-CE variant."""

    def loss(apply_fn, params, batch, rngs, train: bool):
        # apply_fn, not model.apply — see make_fused_causal_lm_loss
        hidden, weight = apply_fn(
            {"params": params}, batch["input_ids"], batch["attention_mask"],
            batch["decoder_input_ids"], batch.get("decoder_attention_mask"),
            deterministic=not train, rngs=rngs,
            method=model.seq2seq_hidden_and_embedding)       # [B,T,H], [V,H]
        labels = batch["labels"]
        token_valid = labels != -100
        if "valid" in batch:
            token_valid = token_valid & (batch["valid"][:, None] > 0)
        safe_labels = jnp.maximum(labels, 0)
        eps = label_smoothing if train else 0.0
        ce = _make_sharded_fused_ce(block_n, block_v, interpret,
                                    label_smoothing=eps)
        per_tok, pred = ce(hidden, weight, safe_labels)
        correct = pred == safe_labels
        return _masked_sums(per_tok, correct, token_valid)

    return loss


def make_fused_mlm_loss(model, mask_cap: float = 0.25, block_n: int = 256,
                        block_v: int = 512, interpret: bool | None = None):
    """MLM CE without the [B, S, V] logits, exploiting MLM's sparsity:
    only ~15% of positions carry labels, so the predicted positions are
    GATHERED into a static-size [K, H] buffer (K = ``mask_cap`` of the
    shard's tokens, block-aligned) and only those go through the blocked
    vocab-CE Pallas kernel (``ops/pallas_vocab_ce.py``) — a ~4x token
    reduction on top of never materializing logits. The decoder bias is
    folded into the SAME verified kernel by augmenting
    ``h → [h | 1 | 0…]`` and ``W → [W | b | 0…]`` (128 lanes to keep
    tiling), so ``h'·W'ᵀ = h·Wᵀ + b`` exactly and the bias cotangent
    falls out of the concat transpose. Selection uses ``lax.top_k`` on
    the validity flags (deterministic, index-stable), per dp shard under
    ``shard_map`` like the causal path. Positions beyond K (never hit at
    the 15% HF masking rate with cap 25%) are dropped from BOTH loss and
    count, keeping the mean consistent."""
    from jax.sharding import PartitionSpec as P

    from huggingface_sagemaker_tensorflow_distributed_tpu.ops.pallas_vocab_ce import (
        fused_vocab_cross_entropy,
    )
    from huggingface_sagemaker_tensorflow_distributed_tpu.parallel.mesh import (
        data_axis_names,
        maybe_current_mesh,
    )

    def loss(apply_fn, params, batch, rngs, train: bool):
        # apply_fn, not model.apply — see make_fused_causal_lm_loss
        hidden, table, bias = apply_fn(
            {"params": params}, batch["input_ids"], batch["attention_mask"],
            token_type_ids=batch.get("token_type_ids"),
            deterministic=not train, rngs=rngs, return_fused_inputs=True,
            **_packed_kwargs(batch))
        labels = batch["labels"]
        token_valid = (labels != -100) & (batch["attention_mask"] > 0)
        if "valid" in batch:
            token_valid = token_valid & (batch["valid"][:, None] > 0)
        safe_labels = jnp.maximum(labels, 0)

        def ce(h, w, b, lab, valid):
            bsz, s, h_dim = h.shape
            n = bsz * s
            k = min(n, -(-int(n * mask_cap) // block_n) * block_n)
            flat_h = h.reshape(n, h_dim)
            flat_valid = valid.reshape(n)
            flat_lab = lab.reshape(n)
            # top_k on the flags: masked positions first, index-stable
            flags, sel = jax.lax.top_k(flat_valid.astype(jnp.int32), k)
            sel_valid = flags > 0
            h_sel = flat_h[sel]
            lab_sel = flat_lab[sel]
            # fold the decoder bias into the matmul: one extra 128-lane
            # block of which only the first column is live
            ones_pad = jnp.concatenate(
                [jnp.ones((k, 1), h_sel.dtype),
                 jnp.zeros((k, 127), h_sel.dtype)], axis=1)
            w_pad = jnp.concatenate(
                [b[:, None].astype(w.dtype),
                 jnp.zeros((w.shape[0], 127), w.dtype)], axis=1)
            per_tok, pred = fused_vocab_cross_entropy(
                jnp.concatenate([h_sel, ones_pad], axis=1),
                jnp.concatenate([w, w_pad], axis=1),
                lab_sel, block_n=block_n, block_v=block_v,
                interpret=interpret)
            return per_tok, pred, lab_sel, sel_valid

        mesh = maybe_current_mesh()
        batch_axes = data_axis_names()
        if mesh is not None and any(
                mesh.shape.get(a, 1) > 1 for a in batch_axes):
            # check_vma=False: pallas_call does not annotate varying-mesh
            # axes on its outputs, which the default vma check rejects
            ce = jax.shard_map(
                ce, mesh=mesh,
                in_specs=(P(batch_axes), P(), P(), P(batch_axes),
                          P(batch_axes)),
                out_specs=(P(batch_axes), P(batch_axes),
                           P(batch_axes), P(batch_axes)),
                check_vma=False)
        per_tok, pred, lab_sel, sel_valid = ce(hidden, table, bias,
                                               safe_labels, token_valid)
        correct = pred == lab_sel
        loss_val, sums = _masked_sums(per_tok, correct, sel_valid)
        # supervision dropped by the static cap (0 whenever the masking
        # rate stays under mask_cap, the designed regime) — surfaced so
        # an over-aggressive mlm_probability is measurable, not silent
        sums["ce_dropped"] = (jnp.sum(token_valid.astype(jnp.float32))
                              - sums["count"])
        return loss_val, sums

    return loss


TASK_LOSSES: dict[str, Callable] = {
    "seq-cls": seq_cls_loss,
    "token-cls": token_cls_loss,
    "qa": qa_loss,
    "seq2seq": seq2seq_loss,
    "causal-lm": causal_lm_loss,
    # masked-LM: CE over the vocab at the masked positions only —
    # exactly the token-cls shape (labels -100 everywhere else), but
    # without the NER F1 (vocab id 0 is a token, not the O tag)
    "mlm": functools.partial(token_cls_loss, with_f1=False),
    "rtd": rtd_loss,
}


class Trainer:
    """Explicit train/eval engine over a device mesh.

    One code path for 1 chip → multi-host pod: the mesh shape is the only
    difference (the ambient-distribution stance of SURVEY.md §7, modeled
    on ``singe_node_train.py:40-41``'s strategy scope rather than
    ``train.py``'s rank juggling).
    """

    def __init__(
        self,
        config: TrainConfig,
        model,
        params: Any,
        mesh: Mesh,
        task: Optional[str] = None,
        total_steps: Optional[int] = None,
    ):
        self.config = config
        self.model = model
        self.mesh = mesh
        self.task = task or config.task
        if self.task not in TASK_LOSSES:
            raise ValueError(f"no loss for task {self.task!r}")
        self.loss_fn = TASK_LOSSES[self.task]
        if getattr(config, "label_smoothing", 0.0) > 0:
            # config validation restricts the knob to task='seq2seq'
            self.loss_fn = make_smoothed_seq2seq_loss(config.label_smoothing)
        if getattr(config, "fused_vocab_ce", False):
            if self.task == "causal-lm" and hasattr(model,
                                                    "hidden_and_embedding"):
                self.loss_fn = make_fused_causal_lm_loss(model)
            elif self.task == "mlm" and "return_fused_inputs" in (
                    inspect.signature(model.__call__).parameters):
                self.loss_fn = make_fused_mlm_loss(
                    model, mask_cap=getattr(config, "fused_mlm_mask_cap",
                                            0.25))
            elif self.task == "seq2seq" and hasattr(
                    model, "seq2seq_hidden_and_embedding"):
                self.loss_fn = make_fused_seq2seq_loss(
                    model, label_smoothing=config.label_smoothing)
            else:
                raise ValueError(
                    "fused_vocab_ce requires task='causal-lm' with a model "
                    "exposing hidden_and_embedding (GPT-2 family), "
                    "task='mlm' with a return_fused_inputs-capable MLM "
                    "model (BERT-family), or task='seq2seq' with a model "
                    "exposing seq2seq_hidden_and_embedding (T5/BART)")
        self.n_chips = world_size(mesh)
        self.dp_size = data_parallel_size(mesh)
        # MoE models sow per-layer load-balance losses into the "losses"
        # collection (models/moe.py); the train step applies with that
        # collection mutable and adds every sowed value to the task loss.
        self._has_sown_losses = (
            getattr(getattr(model, "config", None), "num_experts", 0) or 0) > 0
        # anomaly plane (obs/anomaly.py): the jitted step only computes
        # the grad-norm reduction when a detector will actually read it
        # — un-instrumented runs pay nothing (captured at trace time,
        # consistent with every other opt-in obs cost here)
        from huggingface_sagemaker_tensorflow_distributed_tpu.obs.anomaly import (
            anomaly_enabled_env,
        )
        self._emit_grad_norm = obs.configured() and anomaly_enabled_env()

        self.tx, self.scaled_lr = build_optimizer(
            config, world_size=self.dp_size, total_steps=total_steps)

        # LoRA (models/lora.py): params become {"model": frozen base,
        # "lora": adapters}; the loss merges W + (alpha/r)·A·B inside the
        # jitted step (stop_gradient on the base — XLA drops its grad
        # tree), and the optimizer runs on the adapters only, so no Adam
        # m/v mirrors exist for the base model.
        self._lora_scaling = None
        if getattr(config, "lora_rank", 0) > 0:
            from huggingface_sagemaker_tensorflow_distributed_tpu.models.lora import (
                count_params,
                freeze_except,
                init_lora_params,
                lora_scaling,
                merge_lora,
                trainable_labels,
            )

            lora = init_lora_params(params, config.lora_rank,
                                    config.lora_targets, seed=config.seed)
            self._lora_scaling = lora_scaling(config.lora_rank,
                                              config.lora_alpha)
            head_rx = config.lora_train_heads
            base_labels = trainable_labels(params, head_rx)
            n_heads = sum(int(np.prod(p.shape)) for p, lab in zip(
                jax.tree.leaves(params), jax.tree.leaves(base_labels))
                if lab == "train")
            logger.info(
                "LoRA r=%d alpha=%g targets=%s: %d adapter + %d head "
                "trainable / %d frozen params", config.lora_rank,
                config.lora_alpha, config.lora_targets, count_params(lora),
                n_heads, count_params(params) - n_heads)
            params = {"model": params, "lora": lora}

            inner_loss, scaling = self.loss_fn, self._lora_scaling

            def lora_loss(apply_fn, split, batch, rngs, train):
                merged = merge_lora(freeze_except(split["model"], head_rx),
                                    split["lora"], scaling)
                return inner_loss(apply_fn, merged, batch, rngs, train)

            self.loss_fn = lora_loss
            self.tx = optax.multi_transform(
                {"train": self.tx, "freeze": optax.set_to_zero()},
                param_labels={
                    "model": base_labels,
                    "lora": jax.tree.map(lambda _: "train", params["lora"]),
                })

        state = TrainState(
            step=jnp.zeros((), jnp.int32),
            params=params,
            opt_state=self.tx.init(params),
        )
        # Path-based rules shard params AND their optimizer-state mirrors
        # (adam mu/nu paths contain the param path, so the same rules hit).
        self.state_shardings = param_shardings(state, mesh)
        self.state = jax.device_put(state, self.state_shardings)
        # rbg = TPU hardware RNG for dropout keys (config.rng_impl docs)
        self._base_rng = jax.random.key(config.seed, impl=config.rng_impl)
        self._divergence_fn = None  # built lazily, compiled once
        # --keep_best (HF load_best_model_at_end): host snapshot of the
        # best epoch's params + the watched metric's best value
        self._best_params = None
        self._best_metric: Optional[float] = None
        self.best_epoch: Optional[int] = None

        # Batch shardings are inherited from the arrays the batcher
        # device_puts (batch dim over data axes; token dims over ``seq``
        # when present — the pipeline decides per column). Each jitted
        # call runs under use_mesh so trace-time mesh consumers (ring
        # attention) always see THIS trainer's mesh, regardless of other
        # trainers constructed in the same process.
        # NB: the input batch is NOT donated — its int32 buffers can
        # never input-output-alias the f32 state/metrics, so donation
        # would only emit "donated buffers were not usable" warnings.
        # The H2D double buffer's HBM headroom comes from the fit loop
        # dropping batch N's last reference when it rebinds to N+1.
        # graftlint: allow[R3] no static key: state + batch are traced pytrees, the model/config are bound on self._train_step_impl — one compile per trainer (the compile-budget tracker watches it)
        self._train_step = self._published("_train_step", jax.jit(
            self._train_step_impl,
            in_shardings=(self.state_shardings, None),
            out_shardings=(self.state_shardings, None),
            donate_argnums=(0,),
        ))
        # graftlint: allow[R3] no static key: params + batch are traced pytrees, same contract as the train step above
        self._eval_step = self._published("_eval_step", jax.jit(
            self._eval_step_impl,
            in_shardings=(self.state_shardings.params, None),
            out_shardings=None,
        ))

    def check_replica_divergence(self) -> float:
        """Verify parameter replicas agree across the data/seq mesh axes
        (SURVEY.md §5.2). Returns the relative deviation; raises
        ``ReplicaDivergenceError`` beyond ``config.divergence_tol``.
        Called at checkpoint boundaries so a divergent replica can never
        be persisted silently."""
        from huggingface_sagemaker_tensorflow_distributed_tpu.parallel.collectives import (
            ReplicaDivergenceError,
            make_replica_divergence_fn,
        )

        if self._divergence_fn is None:
            # compiled once; reused at every checkpoint boundary
            self._divergence_fn = self._with_mesh(make_replica_divergence_fn(
                self.mesh, self.state_shardings.params))
        rel = float(jax.device_get(self._divergence_fn(self.state.params)))
        obs.scalar("train/replica_divergence", rel)
        if rel > self.config.divergence_tol:
            raise ReplicaDivergenceError(
                f"parameter replicas diverge (relative deviation {rel:.3e} > "
                f"tol {self.config.divergence_tol:.1e}); refusing to "
                "checkpoint — restore from the last good checkpoint")
        return rel

    def _with_mesh(self, fn):
        from huggingface_sagemaker_tensorflow_distributed_tpu.parallel.mesh import (
            use_mesh,
        )

        @functools.wraps(fn)
        def wrapped(*args):
            with use_mesh(self.mesh):
                return fn(*args)

        return wrapped

    def _published(self, attr: str, jitted):
        """``jitted`` under this trainer's mesh, as ``self.<attr>``. Its
        FIRST call leaves the callable and the shapes it ran with in the
        program map's registry (``obs/programs.py``) and then steps
        aside: every later call is the plain wrapped function, so the fit
        loop gains no call and no branch."""
        from huggingface_sagemaker_tensorflow_distributed_tpu.parallel.mesh import (
            use_mesh,
        )

        run = self._with_mesh(jitted)
        mesh = self.mesh

        @functools.wraps(jitted)
        def first(*args):
            obs.programs.register(
                attr.lstrip("_"), jitted, args,
                key={"batch": "x".join(
                    str(n) for n in jax.tree.leaves(args[-1])[0].shape)},
                root=type(self.model).__name__,
                context=lambda: use_mesh(mesh))
            setattr(self, attr, run)
            return run(*args)

        return first

    # -- jitted bodies ------------------------------------------------------

    def _train_step_impl(self, state: TrainState, batch):
        rng = jax.random.fold_in(self._base_rng, state.step)
        rngs = {"dropout": rng}

        # `train/loss` and `train/optimizer` are the program's own scopes
        # (obs/programs.py): the model's modules inside the first keep
        # their own paths
        @jax.named_scope("train/loss")
        def loss_of(params):
            if not self._has_sown_losses:
                loss, sums = self.loss_fn(self.model.apply, params, batch, rngs, True)
                return loss, sums
            sown = []

            def apply_fn(variables, *a, **kw):
                out, mut = self.model.apply(variables, *a, mutable=["losses"], **kw)
                sown.append(mut.get("losses", {}))
                return out

            loss, sums = self.loss_fn(apply_fn, params, batch, rngs, True)
            for leaf in jax.tree.leaves(sown):
                loss = loss + jnp.asarray(leaf, jnp.float32)
            return loss, sums

        (loss, sums), grads = jax.value_and_grad(loss_of, has_aux=True)(state.params)
        with jax.named_scope("train/optimizer"):
            updates, new_opt = self.tx.update(grads, state.opt_state, state.params)
            new_params = optax.apply_updates(state.params, updates)
        new_state = TrainState(step=state.step + 1, params=new_params, opt_state=new_opt)
        metrics = {
            "loss": loss,
            "accuracy": sums["correct"] / jnp.maximum(sums["count"], 1.0),
        }
        if self._emit_grad_norm:
            # one global reduction over the grad tree — fetched only at
            # the loop's existing sync points; the anomaly detector's
            # explosion/NaN signal (obs/anomaly.py)
            metrics["grad_norm"] = optax.global_norm(grads)
        return new_state, metrics

    def _eval_step_impl(self, params, batch):
        _, sums = self.loss_fn(self.model.apply, params, batch, {}, False)
        return sums

    def _best_snapshot(self):
        """Host snapshot of everything --keep_best must preserve. Full
        fine-tune: the whole param tree. LoRA: only what can change —
        the adapter subtree plus the trainable head leaves; the frozen
        base is identical every epoch and stays on device (a multi-GB
        base would otherwise be allgathered+copied per improvement)."""
        if self._lora_scaling is None:
            return _host_snapshot(self.state.params)
        import re as _re

        from flax.traverse_util import flatten_dict

        rx = (_re.compile(self.config.lora_train_heads)
              if self.config.lora_train_heads else None)
        heads = {p: l for p, l in
                 flatten_dict(self.state.params["model"]).items()
                 if rx is not None and rx.search("/".join(map(str, p)))}
        return {"lora": _host_snapshot(self.state.params["lora"]),
                "heads": _host_snapshot(heads)}

    def _restore_best_into_state(self):
        """load_best_model_at_end: put the best snapshot back into the
        live state (sharded), then release the host copy — the live
        state IS the best model from here on."""
        from flax.traverse_util import flatten_dict, unflatten_dict

        if self._lora_scaling is None:
            params = jax.device_put(self._best_params,
                                    self.state_shardings.params)
        else:
            flat = dict(flatten_dict(self.state.params["model"]))
            head_shard = flatten_dict(self.state_shardings.params["model"])
            for p, leaf in self._best_params["heads"].items():
                flat[p] = jax.device_put(leaf, head_shard[p])
            params = {
                "model": unflatten_dict(flat),
                "lora": jax.device_put(self._best_params["lora"],
                                       self.state_shardings.params["lora"]),
            }
        self.state = TrainState(step=self.state.step, params=params,
                                opt_state=self.state.opt_state)
        self._best_params = None

    @property
    def export_params(self):
        """Deployable model params (with LoRA: base + adapters merged —
        what ``save_pretrained``/``generate`` should see). After a
        ``--keep_best`` fit the live state already holds the best
        epoch's weights (``_restore_best_into_state``)."""
        params = self.state.params
        if self._lora_scaling is None:
            return params
        from huggingface_sagemaker_tensorflow_distributed_tpu.models.lora import (
            merge_lora,
        )

        return merge_lora(params["model"], params["lora"],
                          self._lora_scaling)

    # -- host-side loops ----------------------------------------------------

    def fit(self, train_batcher, epochs: Optional[int] = None,
            checkpointer=None, start_epoch: int = 0,
            start_step_in_epoch: int = 0, eval_batcher=None) -> dict:
        """Epoch loop — `model.fit` parity (reference train.py:145-153).

        Returns a Keras-style history dict: per-epoch mean loss/accuracy
        plus ``train_runtime`` (reference ``scripts/train.py:154-165``).

        The loop never blocks on the device per step: metrics stay on
        device and are fetched only at logging/checkpoint sync points and
        epoch end, so batch prep overlaps the async-dispatched step.
        Mid-epoch resume (``start_step_in_epoch``) continues the epoch's
        permutation from the next unseen batch.

        With ``eval_batcher`` (``--eval_each_epoch``/``--keep_best``),
        every epoch ends with an eval pass whose metrics land in the
        history (``eval_loss``/``eval_accuracy`` lists, Keras
        ``validation_data`` shape); ``--keep_best`` additionally
        snapshots the epoch's params to host whenever the watched
        metric (``--best_metric``) improves, and ``export_params``
        serves that snapshot — HF ``load_best_model_at_end``.
        """
        cfg = self.config
        epochs = cfg.epochs if epochs is None else epochs
        # telemetry: spans/metrics stream to <HSTD_TELEMETRY_DIR> when one
        # is configured; watchdogs (compile tracker, heartbeat w/ stall
        # dump) only spin up on instrumented runs so unit-test fits never
        # start background threads
        obs_files = obs.has_sink()
        heartbeat = None
        if obs_files:
            obs.compile_tracker()
            heartbeat = obs.heartbeat().start()
            heartbeat.watch_current_thread()
        # anomaly plane (obs/anomaly.py): NaN/Inf loss, grad explosion,
        # step-time spikes, persistent stragglers — instrumented runs
        # only (obs.configured() is identical on every host, so the
        # detector exists everywhere; only host 0 writes the events)
        detector = obs.anomalies() if obs.configured() else None
        if detector is not None:
            # fresh rolling baselines per fit: a second fit's different
            # step-time regime must not read as a spike
            detector.begin_fit()
        # MFU accounting (obs/flops.py): analytic per-REAL-token train
        # FLOPs for this model/task + the chip's peak → per-window
        # train/mfu series and the history's train_mfu figure
        fpt, dec_fpt = obs.flops.trainer_flops_per_token(
            getattr(self.model, "config", None), self.task,
            cfg.max_seq_length)
        peak = obs.flops.peak_tflops(jax.devices()[0].device_kind)
        meter = StepMeter(n_chips=self.n_chips,
                          sink=obs.metrics() if obs_files else None,
                          flops_per_token=fpt, dec_flops_per_token=dec_fpt,
                          peak_tflops=peak)
        # real-token window accounting: the batcher logs one
        # (tokens, dec_tokens) entry per staged batch; popping one entry
        # per dispatched step keeps attribution EXACT under prefetch /
        # H2D lookahead. × process_count approximates the global figure
        # (shards are balanced by construction). Tokens of excluded
        # (compiling) steps are dropped by the begin_window() reset.
        tok_scale = jax.process_count()
        token_log = getattr(train_batcher, "token_log", None)
        history: dict[str, list] = {"loss": [], "sparse_categorical_accuracy": []}
        steps_per_epoch = train_batcher.steps_per_epoch()
        if cfg.steps_per_epoch:
            steps_per_epoch = min(steps_per_epoch, cfg.steps_per_epoch)
        if start_step_in_epoch >= steps_per_epoch:
            # a mid-epoch checkpoint landed exactly on the epoch boundary
            start_epoch, start_step_in_epoch = start_epoch + 1, 0
        gbs = train_batcher.global_batch_size
        profiling = False
        first_step = True
        # compile-step exclusion beyond the first step: with length
        # bucketing every NEW batch-shape signature recompiles; the meter
        # must not fold that compile into epoch throughput (timing.py)
        track_shapes = bool(getattr(train_batcher, "bucket_sizes", None))
        seen_shapes: set = set()

        def sync(metrics_list):
            with obs.span("train/sync"):
                fetched = jax.device_get(metrics_list)
            window = meter.end_window()
            meter.begin_window()
            if detector is not None:
                if window is not None and window["steps"]:
                    detector.observe_step_time(meter._steps,
                                               window["step_time_s"])
                for m in fetched:
                    detector.observe_loss(meter._steps, float(m["loss"]))
                    if "grad_norm" in m:
                        detector.observe_grad_norm(meter._steps,
                                                   float(m["grad_norm"]))
            return fetched

        if eval_batcher is None and (cfg.keep_best
                                     or cfg.early_stopping_patience > 0):
            logger.warning(
                "keep_best/early_stopping_patience are set but fit() got "
                "no eval_batcher — both are inert this run (pass "
                "eval_batcher=..., as scripts/train.py does)")
        epochs_since_best = 0
        # the telemetry epilogue must run even when fit raises mid-epoch
        # (OOM, failed save): an armed stall watchdog over a dead loop
        # would emit a false "blocked thread" dump to the post-mortem
        # artifact, and the fit's spans would never reach trace.json
        obs_epilogue = contextlib.ExitStack()

        def _obs_fit_done():
            if heartbeat is not None:
                heartbeat.unwatch()
            if obs_files:
                # a fit's end may lie inside a window somebody times: the
                # program maps wait for obs.shutdown() or a caller's own
                # obs.flush()
                obs.flush(program_maps=False)

        obs_epilogue.callback(_obs_fit_done)
        with obs_epilogue, Stopwatch() as sw:
            for epoch in range(start_epoch, epochs):
                start_step = start_step_in_epoch if epoch == start_epoch else 0
                device_metrics: list = []
                losses, accs = [], []

                if token_log is not None:
                    # a batch staged last epoch but never dispatched
                    # (steps_per_epoch cap) would misalign every pop
                    token_log.clear()
                # close() in finally: early exit (steps_per_epoch cap) and
                # exceptions (OOM, failed checkpoint save) must both stop
                # the prefetch thread, or it keeps transferring batches
                batch_iter = train_batcher.global_arrays(epoch, start_step)
                meter.begin_window()
                try:
                    for step, batch in enumerate(batch_iter, start=start_step):
                        if step >= steps_per_epoch:
                            break
                        if cfg.profile and not profiling and epoch == start_epoch \
                                and step - start_step == 3:
                            jax.profiler.start_trace(cfg.profile_dir)
                            profiling = True
                        recompile = False
                        if track_shapes:
                            sig = tuple(v.shape for v in batch.values())
                            if sig not in seen_shapes:
                                seen_shapes.add(sig)
                                recompile = not first_step
                        if recompile:
                            # close the running window at a sync point
                            # BEFORE dispatching the compiling step, so
                            # steady-state throughput never absorbs it
                            if device_metrics:
                                jax.block_until_ready(
                                    device_metrics[-1]["loss"])
                            meter.end_window()
                        with obs.span("train/step_dispatch"):
                            self.state, metrics = self._train_step(
                                self.state, batch)
                        device_metrics.append(metrics)
                        meter.window_step(gbs)
                        if token_log:
                            tok, dec = token_log.popleft()
                            meter.window_tokens(tok * tok_scale,
                                                dec * tok_scale)
                        obs.pulse()
                        if first_step or recompile:
                            # exclude XLA compile from the throughput window
                            with obs.span("xla/compile_wait"):
                                jax.block_until_ready(metrics["loss"])
                            meter.exclude_step(gbs)
                            # begin_window resets the window's token
                            # counters too — the compile batch's tokens
                            # (popped above) are dropped with its time
                            meter.begin_window()
                            first_step = False
                        if profiling and step - start_step == 6:
                            jax.block_until_ready(metrics["loss"])
                            jax.profiler.stop_trace()
                            profiling = False
                        want_log = cfg.log_every_steps and step % cfg.log_every_steps == 0
                        want_ckpt = (checkpointer is not None and cfg.checkpoint_every_steps
                                     and (step + 1) % cfg.checkpoint_every_steps == 0)
                        if want_log or want_ckpt:
                            for m in sync(device_metrics):
                                losses.append(float(m["loss"]))
                                accs.append(float(m["accuracy"]))
                            device_metrics = []
                        if want_log:
                            logger.info(
                                "epoch %d step %d/%d loss %.4f acc %.4f (%.1f samples/s/chip)",
                                epoch, step, steps_per_epoch, losses[-1], accs[-1],
                                meter.samples_per_sec_per_chip)
                            gstep = epoch * steps_per_epoch + step
                            obs.scalar("train/loss", losses[-1], gstep)
                            obs.scalar("train/accuracy", accs[-1], gstep)
                            obs.scalar("train/samples_per_sec_per_chip",
                                       meter.samples_per_sec_per_chip, gstep)
                        if want_ckpt:
                            if cfg.check_divergence:
                                self.check_replica_divergence()
                            # checkpoint wall time is not step time:
                            # bracket it out of the throughput window
                            # (and the spike detector's series)
                            meter.end_window()
                            with obs.span("train/checkpoint"):
                                checkpointer.save(self.state, epoch=epoch,
                                                  step_in_epoch=step + 1)
                            meter.begin_window()
                finally:
                    if hasattr(batch_iter, "close"):
                        batch_iter.close()

                for m in sync(device_metrics):
                    losses.append(float(m["loss"]))
                    accs.append(float(m["accuracy"]))
                # the epoch boundary's eval/checkpoint/collective time is
                # NOT step time: discard the freshly-begun empty window
                # so none of it reaches throughput or the spike detector
                # (the next epoch's loop opens a fresh one)
                meter.end_window()
                history["loss"].append(float(np.mean(losses)) if losses else float("nan"))
                history["sparse_categorical_accuracy"].append(
                    float(np.mean(accs)) if accs else float("nan"))
                logger.info("epoch %d done: loss %.4f acc %.4f", epoch,
                            history["loss"][-1],
                            history["sparse_categorical_accuracy"][-1])
                obs.scalar("train/epoch_loss", history["loss"][-1], epoch)
                if obs.configured():
                    # straggler visibility: every host reports its mean
                    # step time; rank 0 records min/max/mean. The gather
                    # is a collective, so the guard must agree across
                    # hosts — obs.configured() is env-driven and set
                    # identically on every host by the launcher (unlike
                    # has_sink, which is host-0-only).
                    from huggingface_sagemaker_tensorflow_distributed_tpu.parallel.distributed import (
                        host_step_stats,
                    )
                    stats = host_step_stats(meter.avg_step_time)
                    if stats is not None:
                        obs.scalar("train/step_time_hosts_mean",
                                   stats["mean"], epoch, args=stats)
                        if detector is not None:
                            # straggler alert (ROADMAP): ratio above
                            # HSTD_STRAGGLER_ALERT for 2 consecutive
                            # epochs → one anomaly naming the slow host
                            detector.observe_straggler(epoch, stats)
                from huggingface_sagemaker_tensorflow_distributed_tpu.obs.watchdog import (
                    compile_budget_env,
                )
                if (compile_budget_env() is not None
                        and jax.process_count() > 1
                        and not obs.compile_budget_agreed()):
                    # multi-host ladder capping (ROADMAP): the budget is
                    # crossed at a host-local instant, so the crossing
                    # is AGREED at the epoch boundary — a collective
                    # whose guard (env-driven budget, process_count,
                    # the collectively-latched agreed flag) is
                    # identical on every host. Once latched, every
                    # host's bucket ladder stops minting new widths
                    # from the same step.
                    from huggingface_sagemaker_tensorflow_distributed_tpu.parallel.distributed import (
                        agree_compile_budget_crossed,
                    )
                    if agree_compile_budget_crossed(
                            obs.compile_budget_exceeded()):
                        obs.set_compile_budget_agreed()
                        logger.info(
                            "compile budget crossing agreed across %d "
                            "hosts at epoch %d: bucket ladders stop "
                            "minting new widths", jax.process_count(),
                            epoch)
                stop_early = False
                if eval_batcher is not None:
                    res = self.evaluate(eval_batcher)
                    history.setdefault("eval_loss", []).append(
                        res["eval_loss"])
                    history.setdefault("eval_accuracy", []).append(
                        res["eval_accuracy"])
                    logger.info("epoch %d eval: loss %.4f acc %.4f", epoch,
                                res["eval_loss"], res["eval_accuracy"])
                    obs.scalar("eval/loss", res["eval_loss"], epoch)
                    obs.scalar("eval/accuracy", res["eval_accuracy"], epoch)
                    track_best = (cfg.keep_best
                                  or cfg.early_stopping_patience > 0)
                    if track_best:
                        metric = res[cfg.best_metric]
                        if self._best_metric is None:
                            better = True
                        elif cfg.best_metric.endswith("accuracy"):
                            better = metric > self._best_metric
                        else:
                            better = metric < self._best_metric
                        if better:
                            self._best_metric = metric
                            self.best_epoch = epoch
                            epochs_since_best = 0
                            if cfg.keep_best:
                                # host snapshot: device HBM holds ONE
                                # live state; best params go to host RAM
                                self._best_params = self._best_snapshot()
                            logger.info(
                                "epoch %d is the new best (%s %.4f)",
                                epoch, cfg.best_metric, metric)
                        else:
                            epochs_since_best += 1
                            patience = cfg.early_stopping_patience
                            if patience and epochs_since_best >= patience:
                                logger.info(
                                    "early stop at epoch %d: no %s "
                                    "improvement for %d epochs", epoch,
                                    cfg.best_metric, patience)
                                stop_early = True
                if checkpointer is not None:
                    if cfg.check_divergence:
                        self.check_replica_divergence()
                    checkpointer.save(self.state, epoch=epoch + 1)
                if stop_early:
                    break
            if profiling:  # epoch shorter than the profiled step range
                jax.profiler.stop_trace()
            if cfg.keep_best and self._best_params is not None:
                # load_best_model_at_end, literally: everything after fit
                # (final eval, ROUGE/QA passes, export, adapter sidecar)
                # sees the best epoch's weights. Optimizer state is NOT
                # rewound — training is over; resuming from a checkpoint
                # uses the checkpointed state, not this restore.
                self._restore_best_into_state()
                logger.info("restored best epoch %d params into the live "
                            "state (%s %.4f)", self.best_epoch,
                            cfg.best_metric, self._best_metric)
            meter.end_window()

        history["train_runtime"] = sw.elapsed
        history["train_samples_per_second"] = round(meter.samples_per_sec, 3)
        history["train_samples_per_second_per_chip"] = round(
            meter.samples_per_sec_per_chip, 3)
        achieved = meter.achieved_tflops_per_chip
        if achieved is not None:
            history["train_achieved_tflops_per_chip"] = round(achieved, 6)
            if meter.mfu is not None:
                history["train_mfu"] = round(meter.mfu, 5)
        if obs_files:
            obs.scalar("train/runtime", sw.elapsed)
            obs.scalar("train/samples_per_sec_per_chip_final",
                       meter.samples_per_sec_per_chip)
            obs.scalar("train/compile_excluded_steps", meter.excluded_steps)
            if meter.mfu is not None:
                obs.scalar("train/mfu_final", meter.mfu)
        return history

    def evaluate(self, eval_batcher) -> dict:
        """`model.evaluate` parity (reference train.py:170) with exact
        cross-host aggregation: sums are reduced globally inside jit, so
        every host reports identical numbers (the reference instead
        evaluates the full test set redundantly on every rank).

        Steps are async-dispatched so batch prep overlaps device compute
        like ``fit``, with results drained in fixed-size chunks — the
        dispatch backlog (and the device memory its queued input batches
        pin) stays bounded on arbitrarily large eval sets. The ``finally``
        stops the prefetch producer on any mid-eval failure."""
        chunk = 64
        totals: dict[str, float] = {}

        def drain(device_sums):
            with obs.span("eval/sync"):
                fetched = jax.device_get(device_sums)
            for sums in fetched:
                for key, val in sums.items():
                    totals[key] = totals.get(key, 0.0) + float(val)

        device_sums: list = []
        batch_iter = eval_batcher.global_arrays(epoch=0)
        try:
            with obs.span("eval/run"):
                for batch in batch_iter:
                    device_sums.append(
                        self._eval_step(self.state.params, batch))
                    obs.pulse()
                    if len(device_sums) >= chunk:
                        drain(device_sums)
                        device_sums = []
        finally:
            if hasattr(batch_iter, "close"):
                batch_iter.close()
        drain(device_sums)
        count = max(totals.get("count", 0.0), 1.0)
        results = {"eval_loss": totals.get("loss_sum", 0.0) / count,
                   "eval_accuracy": totals.get("correct", 0.0) / count}
        if "f1_tp" in totals:
            # micro-F1 over the non-O classes, aggregated exactly across
            # hosts/batches from the jitted sums
            tp, fp, fn = (totals["f1_tp"], totals["f1_fp"], totals["f1_fn"])
            results["eval_f1"] = 2 * tp / max(2 * tp + fp + fn, 1.0)
        return results

    # -- results emission (reference train.py:154-179) ----------------------

    def write_train_results(self, history: dict) -> None:
        write_results_file(self.config.output_data_dir, "train_results.txt",
                           history, logger=logger)

    def write_eval_results(self, results: dict) -> None:
        write_results_file(self.config.output_data_dir, "eval_results.txt",
                           results, logger=logger)
