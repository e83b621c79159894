"""Auto-model construction and HF-layout export.

TPU-native replacement for the reference's model load/save surface:
``AutoTokenizer.from_pretrained`` + ``TFAutoModelForSequenceClassification
.from_pretrained`` (reference ``scripts/train.py:69,117``) and
``save_pretrained`` of model+tokenizer (``scripts/train.py:182-183``).

``from_pretrained(path, task=...)`` reads ``config.json`` to pick the
architecture family, builds the matching Flax module + config, initializes
the full param tree (fresh task head), and overlays the converted
checkpoint weights. ``save_pretrained(...)`` writes ``model.safetensors``
(+ ``config.json``) in HF layout so artifacts are loadable by the HF
ecosystem — the same interchange contract the reference relies on.

Offline-first: paths are local directories (this environment has no
network egress); a hub name with no local directory raises with a clear
message. ``from_scratch=True`` (or config-only dirs) skips weight load.
"""

from __future__ import annotations

import json
import os
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from huggingface_sagemaker_tensorflow_distributed_tpu.models import (
    albert,
    bart,
    bert,
    deberta,
    deepseek_v2,
    distilbert,
    electra,
    gpt2,
    llama,
    olmo_hybrid,
    roberta,
    t5,
    xing4,
)
from huggingface_sagemaker_tensorflow_distributed_tpu.models.convert import (
    hf_to_params,
    load_hf_config,
    load_hf_state_dict,
    merge_into,
    params_to_hf,
)
from huggingface_sagemaker_tensorflow_distributed_tpu.models.layers import EncoderConfig
from huggingface_sagemaker_tensorflow_distributed_tpu.utils.logging import get_logger

logger = get_logger(__name__)

# (family, task) → model class
MODEL_REGISTRY: dict[tuple[str, str], Any] = {
    ("bert", "seq-cls"): bert.BertForSequenceClassification,
    ("bert", "token-cls"): bert.BertForTokenClassification,
    ("bert", "qa"): bert.BertForQuestionAnswering,
    ("roberta", "seq-cls"): roberta.RobertaForSequenceClassification,
    ("roberta", "token-cls"): roberta.RobertaForTokenClassification,
    ("roberta", "qa"): roberta.RobertaForQuestionAnswering,
    ("distilbert", "seq-cls"): distilbert.DistilBertForSequenceClassification,
    ("distilbert", "token-cls"): distilbert.DistilBertForTokenClassification,
    ("distilbert", "qa"): distilbert.DistilBertForQuestionAnswering,
    ("electra", "seq-cls"): electra.ElectraForSequenceClassification,
    ("electra", "token-cls"): electra.ElectraForTokenClassification,
    ("electra", "qa"): electra.ElectraForQuestionAnswering,
    ("albert", "seq-cls"): albert.AlbertForSequenceClassification,
    ("albert", "token-cls"): albert.AlbertForTokenClassification,
    ("albert", "qa"): albert.AlbertForQuestionAnswering,
    ("t5", "seq2seq"): t5.T5ForConditionalGeneration,
    ("gpt2", "causal-lm"): gpt2.Gpt2LMHeadModel,
    ("llama", "causal-lm"): llama.LlamaForCausalLM,
    ("deepseek_v2", "causal-lm"): deepseek_v2.DeepseekV2ForCausalLM,
    ("olmo_hybrid", "causal-lm"): olmo_hybrid.OlmoHybridForCausalLM,
    ("xing4_0", "causal-lm"): xing4.Xing4ForCausalLM,
    ("bert", "mlm"): bert.BertForMaskedLM,
    ("roberta", "mlm"): roberta.RobertaForMaskedLM,
    ("distilbert", "mlm"): distilbert.DistilBertForMaskedLM,
    ("albert", "mlm"): albert.AlbertForMaskedLM,
    ("deberta-v2", "seq-cls"): deberta.DebertaV2ForSequenceClassification,
    ("deberta-v2", "token-cls"): deberta.DebertaV2ForTokenClassification,
    ("deberta-v2", "qa"): deberta.DebertaV2ForQuestionAnswering,
    ("deberta-v2", "mlm"): deberta.DebertaV2ForMaskedLM,
    ("electra", "rtd"): electra.ElectraForPreTraining,
    ("electra", "mlm"): electra.ElectraForMaskedLM,
    ("bart", "seq2seq"): bart.BartForConditionalGeneration,
    ("mbart", "seq2seq"): bart.BartForConditionalGeneration,
}

CONFIG_BUILDERS = {
    "bert": bert.bert_config_from_hf,
    "roberta": roberta.roberta_config_from_hf,
    "distilbert": distilbert.distilbert_config_from_hf,
    "electra": electra.electra_config_from_hf,
    "albert": albert.albert_config_from_hf,
    "t5": t5.t5_config_from_hf,
    "gpt2": gpt2.gpt2_config_from_hf,
    "llama": llama.llama_config_from_hf,
    "deepseek_v2": deepseek_v2.deepseek_v2_config_from_hf,
    "olmo_hybrid": olmo_hybrid.olmo_hybrid_config_from_hf,
    "xing4_0": xing4.xing4_config_from_hf,
    "deberta-v2": deberta.deberta_config_from_hf,
    "bart": bart.bart_config_from_hf,
    # mBART hardcodes pre-LN + per-stack final LN in its modeling class
    # (not in config.json), so the builder pins the variant flags
    "mbart": lambda hf, **ov: bart.bart_config_from_hf(
        hf, **{"normalize_before": True, "stack_final_ln": True, **ov}),
}

# Our config → HF config.json for export
def _bart_hf_config(c) -> dict:
    return {
        "model_type": "bart", "architectures": ["BartForConditionalGeneration"],
        "vocab_size": c.vocab_size, "d_model": c.d_model,
        "encoder_layers": c.encoder_layers, "decoder_layers": c.decoder_layers,
        "encoder_attention_heads": c.encoder_attention_heads,
        "decoder_attention_heads": c.decoder_attention_heads,
        "encoder_ffn_dim": c.encoder_ffn_dim,
        "decoder_ffn_dim": c.decoder_ffn_dim,
        "activation_function": c.activation_function,
        "dropout": c.dropout, "attention_dropout": c.attention_dropout,
        "activation_dropout": c.activation_dropout,
        "max_position_embeddings": c.max_position_embeddings,
        "init_std": c.init_std, "scale_embedding": c.scale_embedding,
        "pad_token_id": c.pad_token_id, "bos_token_id": c.bos_token_id,
        "eos_token_id": c.eos_token_id,
        "decoder_start_token_id": c.decoder_start_token_id,
        "forced_bos_token_id": c.forced_bos_token_id,
    }


_HF_CONFIG_EXPORTERS = {
    "bert": lambda c: {
        "model_type": "bert", "architectures": ["BertForSequenceClassification"],
        "vocab_size": c.vocab_size, "hidden_size": c.hidden_size,
        "num_hidden_layers": c.num_layers, "num_attention_heads": c.num_heads,
        "intermediate_size": c.intermediate_size,
        "max_position_embeddings": c.max_position_embeddings,
        "type_vocab_size": c.type_vocab_size, "hidden_act": c.hidden_act,
        "layer_norm_eps": c.layer_norm_eps,
        "hidden_dropout_prob": c.hidden_dropout,
        "attention_probs_dropout_prob": c.attention_dropout,
        "pad_token_id": c.pad_token_id, "initializer_range": c.initializer_range,
    },
    "roberta": lambda c: {
        "model_type": "roberta", "architectures": ["RobertaForSequenceClassification"],
        "vocab_size": c.vocab_size, "hidden_size": c.hidden_size,
        "num_hidden_layers": c.num_layers, "num_attention_heads": c.num_heads,
        "intermediate_size": c.intermediate_size,
        "max_position_embeddings": c.max_position_embeddings,
        "type_vocab_size": c.type_vocab_size, "hidden_act": c.hidden_act,
        "layer_norm_eps": c.layer_norm_eps,
        "hidden_dropout_prob": c.hidden_dropout,
        "attention_probs_dropout_prob": c.attention_dropout,
        "pad_token_id": c.pad_token_id, "initializer_range": c.initializer_range,
    },
    "distilbert": lambda c: {
        "model_type": "distilbert", "architectures": ["DistilBertForSequenceClassification"],
        "vocab_size": c.vocab_size, "dim": c.hidden_size,
        "n_layers": c.num_layers, "n_heads": c.num_heads,
        "hidden_dim": c.intermediate_size,
        "max_position_embeddings": c.max_position_embeddings,
        "activation": c.hidden_act, "dropout": c.hidden_dropout,
        "attention_dropout": c.attention_dropout,
        "pad_token_id": c.pad_token_id, "initializer_range": c.initializer_range,
    },
    "albert": lambda c: {
        "model_type": "albert", "architectures": ["AlbertForSequenceClassification"],
        "vocab_size": c.vocab_size, "hidden_size": c.hidden_size,
        "embedding_size": c.embedding_size or c.hidden_size,
        "num_hidden_layers": c.num_layers, "num_attention_heads": c.num_heads,
        "num_hidden_groups": 1, "inner_group_num": 1,
        "classifier_dropout_prob": (
            c.classifier_dropout if c.classifier_dropout is not None
            else c.hidden_dropout),
        "intermediate_size": c.intermediate_size,
        "max_position_embeddings": c.max_position_embeddings,
        "type_vocab_size": c.type_vocab_size, "hidden_act": c.hidden_act,
        "layer_norm_eps": c.layer_norm_eps,
        "hidden_dropout_prob": c.hidden_dropout,
        "attention_probs_dropout_prob": c.attention_dropout,
        "pad_token_id": c.pad_token_id, "initializer_range": c.initializer_range,
    },
    "electra": lambda c: {
        "model_type": "electra", "architectures": ["ElectraForSequenceClassification"],
        "vocab_size": c.vocab_size, "hidden_size": c.hidden_size,
        "embedding_size": c.embedding_size or c.hidden_size,
        "num_hidden_layers": c.num_layers, "num_attention_heads": c.num_heads,
        "intermediate_size": c.intermediate_size,
        "max_position_embeddings": c.max_position_embeddings,
        "type_vocab_size": c.type_vocab_size, "hidden_act": c.hidden_act,
        "layer_norm_eps": c.layer_norm_eps,
        "hidden_dropout_prob": c.hidden_dropout,
        "attention_probs_dropout_prob": c.attention_dropout,
        "pad_token_id": c.pad_token_id, "initializer_range": c.initializer_range,
    },
    "deberta-v2": lambda c: {
        "model_type": "deberta-v2",
        "architectures": ["DebertaV2ForSequenceClassification"],
        "vocab_size": c.vocab_size, "hidden_size": c.hidden_size,
        "num_hidden_layers": c.num_layers, "num_attention_heads": c.num_heads,
        "intermediate_size": c.intermediate_size,
        "max_position_embeddings": c.max_position_embeddings,
        "type_vocab_size": c.type_vocab_size, "hidden_act": c.hidden_act,
        "layer_norm_eps": c.layer_norm_eps,
        "hidden_dropout_prob": c.hidden_dropout,
        "attention_probs_dropout_prob": c.attention_dropout,
        "pooler_dropout": c.pooler_dropout,
        "pooler_hidden_act": c.pooler_hidden_act,
        "pooler_hidden_size": c.hidden_size,
        "pad_token_id": c.pad_token_id,
        "initializer_range": c.initializer_range,
        "embedding_size": c.embedding_size or c.hidden_size,
        "position_biased_input": c.position_biased_input,
        "relative_attention": c.relative_attention,
        "position_buckets": c.position_buckets,
        "max_relative_positions": c.max_relative_positions,
        "share_att_key": c.share_att_key,
        "pos_att_type": list(c.pos_att_type),
        "norm_rel_ebd": c.norm_rel_ebd,
        **({"conv_kernel_size": c.conv_kernel_size,
            "conv_act": c.conv_act, "conv_groups": c.conv_groups}
           if c.conv_kernel_size else {}),
    },
    "gpt2": lambda c: {
        "model_type": "gpt2", "architectures": ["GPT2LMHeadModel"],
        "vocab_size": c.vocab_size, "n_positions": c.max_position_embeddings,
        "n_embd": c.hidden_size, "n_layer": c.num_layers,
        "n_head": c.num_heads, "n_inner": c.intermediate_size,
        "activation_function": c.hidden_act,
        "layer_norm_epsilon": c.layer_norm_eps,
        "resid_pdrop": c.hidden_dropout, "embd_pdrop": c.embd_dropout,
        "attn_pdrop": c.attention_dropout,
        "bos_token_id": c.bos_token_id, "eos_token_id": c.eos_token_id,
        "pad_token_id": c.pad_token_id,
        "initializer_range": c.initializer_range,
    },
    "llama": lambda c: {
        "model_type": c.model_type,
        "architectures": [{"llama": "LlamaForCausalLM",
                           "mistral": "MistralForCausalLM",
                           "qwen2": "Qwen2ForCausalLM",
                           "gemma": "GemmaForCausalLM",
                           "mixtral": "MixtralForCausalLM"}[c.model_type]],
        "vocab_size": c.vocab_size, "hidden_size": c.hidden_size,
        "num_hidden_layers": c.num_layers,
        "num_attention_heads": c.num_heads,
        "num_key_value_heads": c.num_kv_heads,
        "intermediate_size": c.intermediate_size,
        "max_position_embeddings": c.max_position_embeddings,
        "rope_theta": c.rope_theta, "rms_norm_eps": c.rms_norm_eps,
        "hidden_act": c.hidden_act,
        "tie_word_embeddings": c.tie_word_embeddings,
        "bos_token_id": c.bos_token_id, "eos_token_id": c.eos_token_id,
        "pad_token_id": c.pad_token_id,
        "initializer_range": c.initializer_range,
        **({"sliding_window": c.sliding_window} if c.model_type == "mistral"
           else {}),
        **({"sliding_window": c.sliding_window,
            "num_local_experts": c.num_experts,
            "num_experts_per_tok": c.expert_top_k,
            "router_aux_loss_coef": c.router_aux_coef,
            # framework knobs HF Mixtral has no fields for (extra keys
            # are legal in config.json; the builder reads them back)
            "moe_every": c.moe_every,
            "expert_capacity_factor": c.expert_capacity_factor}
           if c.model_type == "mixtral" else {}),
        **({"sliding_window": c.sliding_window or 4096,
            "use_sliding_window": c.sliding_window is not None,
            "max_window_layers": c.sliding_window_start_layer}
           if c.model_type == "qwen2" else {}),
        **({"head_dim": c.resolved_head_dim,
            "hidden_activation": c.hidden_act}
           if c.model_type == "gemma" else {}),
        **({"rope_scaling": c.rope_scaling_dict} if c.rope_scaling
           else {}),
        **({"head_dim": c.head_dim} if c.head_dim is not None
           and c.model_type != "gemma" else {}),
    },
    "bart": _bart_hf_config,
    "mbart": lambda c: {**_bart_hf_config(c), "model_type": "mbart",
                        "architectures": ["MBartForConditionalGeneration"]},
    "t5": lambda c: {
        "model_type": "t5", "architectures": ["T5ForConditionalGeneration"],
        "vocab_size": c.vocab_size, "d_model": c.d_model, "d_kv": c.d_kv,
        "d_ff": c.d_ff, "num_layers": c.num_layers,
        "num_decoder_layers": c.num_decoder_layers, "num_heads": c.num_heads,
        "relative_attention_num_buckets": c.relative_attention_num_buckets,
        "relative_attention_max_distance": c.relative_attention_max_distance,
        "dropout_rate": c.dropout_rate,
        "layer_norm_epsilon": c.layer_norm_epsilon,
        "feed_forward_proj": c.feed_forward_proj,
        "tie_word_embeddings": c.tie_word_embeddings,
        "pad_token_id": c.pad_token_id, "eos_token_id": c.eos_token_id,
        "decoder_start_token_id": c.decoder_start_token_id,
        "initializer_factor": c.initializer_factor,
    },
}


# families whose Encoder stack supports per-layer MoE FFNs / pipelining
# (T5 has its own blocks; ALBERT shares one layer across the stack)
_MOE_FAMILIES = ("bert", "roberta", "distilbert", "electra", "gpt2", "llama")
_PIPELINE_FAMILIES = _MOE_FAMILIES + ("t5", "bart", "mbart")

_MOE_CONFIG_KEYS = ("num_experts", "expert_top_k", "moe_every",
                    "expert_capacity_factor", "router_aux_coef")


# architecturally identical families that ship under their own
# model_type: same modules, same state-dict key layout
_FAMILY_ALIASES = {
    "xlm-roberta": "roberta",   # XLM-R == RoBERTa with a bigger vocab
    "camembert": "roberta",
    # same state-dict layout as Llama; the config builder reads the
    # variant knobs (sliding_window, Qwen2's hardcoded qkv biases) off
    # the original model_type
    "mistral": "llama",
    "qwen2": "llama",
    "gemma": "llama",
    # Mixtral = Mistral attention + a SwiGLU expert bank per layer; the
    # config builder reads the MoE shape off the original model_type
    "mixtral": "llama",
}


def detect_family(hf_config: dict) -> str:
    mt = hf_config.get("model_type", "")
    mt = _FAMILY_ALIASES.get(mt, mt)
    if mt in CONFIG_BUILDERS:
        return mt
    raise ValueError(f"unsupported model_type {mt!r} (supported: "
                     f"{sorted(CONFIG_BUILDERS) + sorted(_FAMILY_ALIASES)})")


def build_model(family: str, task: str, config: EncoderConfig, num_labels: int = 2):
    cls = MODEL_REGISTRY.get((family, task))
    if cls is None:
        raise ValueError(f"no model for family={family!r} task={task!r}")
    if task in ("qa", "seq2seq", "causal-lm", "mlm", "rtd"):
        return cls(config)
    return cls(config, num_labels=num_labels)


def init_params(model, config=None, seed: int = 0, seq_len: int = 8):
    rng = jax.random.PRNGKey(seed)
    dummy = jnp.ones((1, seq_len), jnp.int32)
    mask = jnp.ones((1, seq_len), jnp.int32)
    if getattr(model, "is_encoder_decoder", False):
        variables = model.init(rng, dummy, mask, dummy, mask)
    else:
        variables = model.init(rng, dummy, mask)
    return variables["params"]


def from_pretrained(
    model_name_or_path: str,
    task: str = "seq-cls",
    num_labels: int = 2,
    dtype=jnp.float32,
    param_dtype=jnp.float32,
    seed: int = 0,
    from_scratch: bool = False,
    **config_overrides,
):
    """Load (or freshly init) a model. Returns (model, params, family, config)."""
    if not os.path.isdir(model_name_or_path):
        raise FileNotFoundError(
            f"{model_name_or_path!r} is not a local directory. This framework is "
            "offline-first: pass a local checkpoint directory containing "
            "config.json (+ model.safetensors), e.g. produced by "
            "`save_pretrained` or an HF download.")
    hf_config = load_hf_config(model_name_or_path)
    family = detect_family(hf_config)
    wants_moe = (config_overrides.get("num_experts", 0)
                 or hf_config.get("num_experts", 0))
    if wants_moe and family not in _MOE_FAMILIES:
        # T5 has its own config class (no MoE fields) and ALBERT shares
        # ONE layer across the stack (per-layer expert banks can't exist)
        raise ValueError(
            f"MoE (num_experts={wants_moe}) is not supported for "
            f"family {family!r}; supported: {sorted(_MOE_FAMILIES)}")
    wants_pp = config_overrides.get("pipeline_stages", 0)
    if wants_pp and family not in _PIPELINE_FAMILIES:
        raise ValueError(
            f"pipeline_stages={wants_pp} is not supported for family "
            f"{family!r}; supported: {sorted(_PIPELINE_FAMILIES)}")
    wants_kv = config_overrides.get("kv_cache_dtype", "fp")
    if wants_kv != "fp" and family not in ("llama", "gpt2"):
        # fail with names here, not as a TypeError inside a frozen
        # config constructor (same convention as the MoE/pp guards)
        raise ValueError(
            f"kv_cache_dtype={wants_kv!r} is only supported for the "
            f"decoder-only families (llama, gpt2), not {family!r}")
    if family in ("t5", "bart", "mbart") and task != "seq2seq":
        # failing loudly here beats a TypeError deep inside jit tracing
        # when the seq-cls loss feeds an encoder-decoder model
        raise ValueError(
            f"{model_name_or_path!r} is a {family} (encoder-decoder) "
            f"checkpoint; it only supports task='seq2seq', got task={task!r}")
    if (family == "deberta-v2" and task == "mlm"
            and hf_config.get("legacy") is False):
        raise ValueError(
            f"{model_name_or_path!r} uses the non-legacy DeBERTa MLM head "
            "(lm_predictions.lm_head); only the legacy cls.predictions "
            "layout is supported — silently loading would leave a random "
            "head (HF's own non-legacy forward is broken in transformers "
            "4.57: tie_weights clobbers lm_head.dense)")
    if (family in ("gpt2", "llama", "deepseek_v2", "olmo_hybrid", "xing4_0")
            and task != "causal-lm"):
        raise ValueError(
            f"{model_name_or_path!r} is a {family} (decoder-only) "
            f"checkpoint; it only supports task='causal-lm', got "
            f"task={task!r}")
    if family in ("bert", "albert") and task != "seq-cls":
        # HF Bert/Albert QA/token-cls models are built with
        # add_pooling_layer=False; only the seq-cls head uses the pooler.
        config_overrides.setdefault("use_pooler", False)
    if family in _MOE_FAMILIES:
        # a config.json we exported for an MoE model carries the MoE
        # fields — honour them so the expert bank is rebuilt on reload
        for key in _MOE_CONFIG_KEYS:
            if key in hf_config:
                config_overrides.setdefault(key, hf_config[key])
    config = CONFIG_BUILDERS[family](
        hf_config, dtype=dtype, param_dtype=param_dtype, **config_overrides)
    model = build_model(family, task, config, num_labels)
    params = init_params(model, config, seed=seed)
    has_weights = os.path.exists(os.path.join(model_name_or_path, "model.safetensors")) or \
        os.path.exists(os.path.join(model_name_or_path, "pytorch_model.bin"))
    if (family in ("deepseek_v2", "olmo_hybrid", "xing4_0") and has_weights
            and not from_scratch):
        raise ValueError(
            f"{model_name_or_path!r} holds {family} weights: loading a "
            "published checkpoint of this family is not implemented "
            "(models/convert.py has no mapping for it); pass "
            "from_scratch=True for seeded random weights")
    if not from_scratch and has_weights:
        state = load_hf_state_dict(model_name_or_path)
        loaded = hf_to_params(state, family)
        if getattr(config, "pipeline_stages", 0):
            # checkpoints are stored per-layer; the pipelined modules
            # want the layer-stacked tree
            from huggingface_sagemaker_tensorflow_distributed_tpu.models.pipeline import (
                GPT2_LAYER_LEAVES,
                stack_layer_params,
            )

            bb = loaded.get("backbone", {})
            if "encoder" in bb:
                bb = dict(bb)
                bb["pipelined_encoder"] = stack_layer_params(
                    bb.pop("encoder"), config.num_layers)
                loaded = {**loaded, "backbone": bb}
            elif family == "gpt2":
                bb = dict(bb)
                layers = {k: bb.pop(k) for k in list(bb)
                          if k.startswith("h_")}
                bb["pipelined_h"] = stack_layer_params(
                    layers, config.num_layers, GPT2_LAYER_LEAVES, "h_{}")
                loaded = {**loaded, "backbone": bb}
            elif family == "llama":
                from huggingface_sagemaker_tensorflow_distributed_tpu.models.pipeline import (
                    llama_layer_leaves,
                )

                bb = dict(bb)
                layers = {k: bb.pop(k) for k in list(bb)
                          if k.startswith("layers_")}
                bb["pipelined_layers"] = stack_layer_params(
                    layers, config.num_layers,
                    llama_layer_leaves(config.qkv_bias), "layers_{}")
                loaded = {**loaded, "backbone": bb}
            elif family in ("t5", "bart", "mbart"):
                from huggingface_sagemaker_tensorflow_distributed_tpu.models.pipeline import (
                    convert_encdec_stacks,
                )
                loaded = convert_encdec_stacks(loaded, family, config,
                                               to_stacked=True)
        params, missing = merge_into(params, loaded)
        logger.info("loaded %s (%s) — %d fresh head params", model_name_or_path,
                    family, len(missing))
        moe_path = os.path.join(model_name_or_path, "moe.safetensors")
        if os.path.exists(moe_path):
            # sidecar written by save_pretrained for MoE models: expert/
            # router weights under their native param paths
            from safetensors.numpy import load_file
            params, applied = _overlay_flat(params, load_file(moe_path))
            model_moe = {k for k in _flatten_params(params) if "/moe/" in k}
            if applied != model_moe:
                # a moe_every/num_experts override moved the expert
                # layers: refusing beats silently training random experts
                raise ValueError(
                    f"MoE sidecar {moe_path} does not line up with the "
                    f"model's expert layout (sidecar-only: "
                    f"{sorted(set(applied) - model_moe)[:4]}, model-only: "
                    f"{sorted(model_moe - applied)[:4]}); load with the "
                    "checkpoint's own num_experts/moe_every settings")
            logger.info("loaded %d MoE expert weights from %s",
                        len(applied), moe_path)
    else:
        logger.info("initialized %s (%s) from scratch", model_name_or_path, family)
    return model, params, family, config


def _flatten_params(params: Any) -> dict[str, np.ndarray]:
    from flax.traverse_util import flatten_dict

    return {k: np.asarray(v)
            for k, v in flatten_dict(params, sep="/").items()}


def _overlay_flat(params: Any, flat: dict[str, np.ndarray]) -> tuple[Any, set]:
    """Overlay a {native-path: array} dict onto a param tree. Returns
    (params, keys actually applied) so callers can detect sidecar/model
    layout mismatches instead of silently keeping random init."""
    from flax.traverse_util import flatten_dict, unflatten_dict

    tree = flatten_dict(params, sep="/")
    applied = set()
    for key, src in flat.items():
        if key not in tree:
            continue
        if tuple(np.shape(src)) != tuple(np.shape(tree[key])):
            raise ValueError(
                f"shape mismatch at {key}: sidecar {np.shape(src)} "
                f"vs model {np.shape(tree[key])}")
        tree[key] = jnp.asarray(src, dtype=jnp.asarray(tree[key]).dtype)
        applied.add(key)
    return unflatten_dict(tree, sep="/"), applied


def save_pretrained(output_dir: str, params: Any, family: str, config: EncoderConfig,
                    host0_only: bool = True) -> None:
    """Export params in HF layout (reference ``scripts/train.py:182-183``).

    Host-0 gated — the reference saves from every rank (racy on shared
    filesystems; its own comment warns about this, ``scripts/train.py:181``).
    """
    if jax.process_count() > 1:
        # Params may be sharded across non-addressable devices (fsdp/tp
        # spanning hosts): gather to fully-replicated host arrays first.
        # Collective — every host must participate before the host-0 gate.
        from jax.experimental import multihost_utils
        # tiled=True: reassemble each param's GLOBAL value (tiled=False
        # stacks per-process copies, and is unsupported for arrays whose
        # shards span processes)
        params = multihost_utils.process_allgather(params, tiled=True)
    if host0_only and jax.process_index() != 0:
        return
    os.makedirs(output_dir, exist_ok=True)
    params = jax.device_get(params)
    if getattr(config, "pipeline_stages", 0):
        # stacked → per-layer so the HF reverse rules apply
        from huggingface_sagemaker_tensorflow_distributed_tpu.models.pipeline import (
            GPT2_LAYER_LEAVES,
            unstack_layer_params,
        )

        bb = params.get("backbone", {})
        if "pipelined_encoder" in bb:
            bb = dict(bb)
            bb["encoder"] = unstack_layer_params(
                bb.pop("pipelined_encoder"), config.num_layers)
            params = {**params, "backbone": bb}
        elif "pipelined_h" in bb:
            bb = dict(bb)
            bb.update(unstack_layer_params(
                bb.pop("pipelined_h"), config.num_layers,
                GPT2_LAYER_LEAVES, "h_{}"))
            params = {**params, "backbone": bb}
        elif "pipelined_layers" in bb:
            from huggingface_sagemaker_tensorflow_distributed_tpu.models.pipeline import (
                llama_layer_leaves,
            )

            bb = dict(bb)
            bb.update(unstack_layer_params(
                bb.pop("pipelined_layers"), config.num_layers,
                llama_layer_leaves(config.qkv_bias), "layers_{}"))
            params = {**params, "backbone": bb}
        elif family in ("t5", "bart", "mbart"):
            from huggingface_sagemaker_tensorflow_distributed_tpu.models.pipeline import (
                convert_encdec_stacks,
            )
            params = convert_encdec_stacks(params, family, config,
                                           to_stacked=False)
    state = params_to_hf(params, family)
    state = {k: np.ascontiguousarray(v) for k, v in state.items()}
    from safetensors.numpy import save_file
    save_file(state, os.path.join(output_dir, "model.safetensors"),
              metadata={"format": "pt"})
    cfg_dict = _HF_CONFIG_EXPORTERS[family](config)
    if getattr(config, "num_experts", 0) and family != "llama":
        # expert/router weights have no HF-layout counterpart: persist
        # them in a sidecar under native paths, and record the MoE shape
        # in config.json so from_pretrained rebuilds the expert bank.
        # (Mixtral/llama is the exception: HF DOES define an expert
        # layout, so params_to_hf exports the bank into
        # model.safetensors directly — no sidecar.)
        moe_state = {k: np.ascontiguousarray(v)
                     for k, v in _flatten_params(params).items()
                     if "/moe/" in k}
        save_file(moe_state, os.path.join(output_dir, "moe.safetensors"))
        for key in _MOE_CONFIG_KEYS:
            cfg_dict[key] = getattr(config, key)
    with open(os.path.join(output_dir, "config.json"), "w") as f:
        json.dump(cfg_dict, f, indent=2)
    logger.info("exported HF-layout checkpoint to %s", output_dir)
