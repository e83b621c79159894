"""Typed configuration: hyperparameters, environment contract, CLI.

Capability parity with the reference's three-tier config layer
(launcher dict → platform-serialized CLI strings → argparse with env
defaults; reference ``launch.py:13-18`` and ``scripts/train.py:36-52``),
rebuilt as ONE typed dataclass with validated parsing. This fixes by
construction the reference's stringly-typed bugs:

- ``--learning_rate`` declared ``type=str`` (reference
  ``scripts/train.py:43``) so ``lr * world_size`` performs string
  repetition when the flag is passed → here it is a float.
- ``--do_train``/``--do_eval`` declared ``type=bool`` (reference
  ``scripts/train.py:44-45``) so ``--do_train False`` is truthy → here
  booleans parse "true/false/1/0" properly.

Environment contract: the reference consumes SageMaker's ``SM_OUTPUT_DATA_DIR``,
``SM_MODEL_DIR``, ``SM_NUM_GPUS`` (``scripts/train.py:48-50``). We honour the
same variables for drop-in compatibility and add TPU-native equivalents
(``TPU_OUTPUT_DATA_DIR``, ``TPU_MODEL_DIR``) plus multi-host coordination
variables (``TPU_COORDINATOR_ADDRESS``, ``TPU_NUM_PROCESSES``,
``TPU_PROCESS_ID``) consumed by ``parallel.distributed``.

Unknown CLI args are tolerated (``parse_known_args``), matching the
reference's tolerance of platform-injected extras (``scripts/train.py:52``).
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
from dataclasses import dataclass, field, fields
from typing import Optional


def _parse_bool(v) -> bool:
    if isinstance(v, bool):
        return v
    s = str(v).strip().lower()
    if s in ("true", "1", "yes", "y"):
        return True
    if s in ("false", "0", "no", "n", ""):
        return False
    raise argparse.ArgumentTypeError(f"expected a boolean, got {v!r}")


# task heads that stay fully trainable under LoRA by default (PEFT
# ``modules_to_save`` analogue); single source of truth — models/lora.py
# re-exports this as ``HEAD_REGEX_DEFAULT``
LORA_HEAD_REGEX_DEFAULT = r"(classifier|qa_outputs|pooler)"


def _env(*names: str, default: Optional[str] = None) -> Optional[str]:
    for name in names:
        if name in os.environ:
            return os.environ[name]
    return default


@dataclass
class TrainConfig:
    """All knobs for a fine-tuning job.

    Field names follow the reference's hyperparameter contract
    (``launch.py:13-18``: epochs, train_batch_size, eval_batch_size,
    model_name_or_path) so launcher dicts are drop-in compatible.
    """

    # --- model / task ---
    model_name_or_path: str = "bert-base-uncased"
    task: str = "seq-cls"          # seq-cls | token-cls | qa | seq2seq |
                                   # causal-lm | mlm | rtd
    num_labels: int = 2
    max_seq_length: int = 512      # reference pads to tokenizer.model_max_length=512 (train.py:81)
    max_target_length: int = 64    # seq2seq decoder length (summaries are short)
    # T5 pretraining: corrupt spans of the input text instead of a
    # source/target dataset (task stays seq2seq; any text source works)
    span_corruption: bool = False
    # seq2seq eval extra: greedy-generate this many eval examples and
    # report ROUGE-L alongside loss/accuracy (0 = off; generation is a
    # separate pass, so this scales eval cost with the sample count)
    eval_rouge_samples: int = 0
    # qa eval extra: decode predicted answer TEXTS for this many eval
    # examples and report SQuAD exact-match/F1 alongside span accuracy
    # (0 = off; one extra forward pass over the sampled examples)
    eval_qa_samples: int = 0
    # fused-MLM static gather capacity as a fraction of each shard's
    # tokens; must exceed the dataset's masking rate (default 0.15 HF
    # rate → 0.25 cap). Positions beyond the cap are dropped from loss
    # AND count (surfaced as the ce_dropped metric) — raise this when
    # pretraining with a higher mlm_probability
    fused_mlm_mask_cap: float = 0.25
    # pin MLM masks to the seed draw for every epoch (pre-r4 behavior;
    # ablation knob — default re-draws per epoch like HF's collator)
    mlm_static_masking: bool = False
    # causal-lm pretraining: pack documents EOS-joined into completely
    # full rows (zero pad waste — every MXU cycle on real tokens)
    packed_sequences: bool = False
    # token packing WITH per-example boundaries (data/pipeline.py::
    # pack_examples): short examples share rows behind segment ids +
    # restarting positions, attention stays block-diagonal per example
    # (cross-contamination-safe) and loss/metrics match unpacked exactly
    # — the pad-waste fix for fine-tuning corpora where packed_sequences'
    # cross-document attention is not acceptable. causal-lm and mlm.
    segment_packing: bool = False
    from_scratch: bool = False     # random init instead of pretrained weights

    # --- data ---
    dataset: str = "imdb"          # imdb | sst2 | conll2003 | squad | cnn_dailymail | synthetic
    dataset_path: Optional[str] = None   # local dataset dir (offline mode)
    # stream the corpus from disk instead of materializing it densely in
    # host RAM (mlm / causal-lm / seq-cls; fixes the reference's
    # materialize-everything quirk at scripts/train.py:80-83). Train-side
    # only; eval sets stay materialized (they're small and need ROUGE/EM
    # decoding access)
    streaming: bool = False
    max_train_samples: Optional[int] = None
    max_eval_samples: Optional[int] = None

    # --- optimization (reference defaults: train.py:39-43) ---
    epochs: int = 3
    train_batch_size: int = 8      # per-worker, as in the reference (launch.py:15)
    eval_batch_size: int = 4
    learning_rate: float = 5e-5
    scale_lr_by_world_size: bool = True   # reference semantics: lr × hvd.size() (train.py:112)
    # adamw default (adam = exact reference parity, coupled, no decay);
    # adafactor = T5's sublinear-memory pretraining optimizer (no
    # weight_decay); lamb = large-batch (pod-scale) BERT
    optimizer: str = "adamw"       # adamw | adam | adafactor | lamb
    # bf16 storage for Adam's m/v buffers (fp32 compute each step):
    # halves optimizer HBM — batch-size headroom at the 16G ceiling.
    # adam/adamw only (adafactor is already sublinear; lamb unsupported)
    optimizer_state_dtype: str = "float32"   # float32 | bfloat16
    lr_schedule: str = "linear"    # linear | cosine (with warmup_ratio > 0)
    warmup_ratio: float = 0.0
    weight_decay: float = 0.0
    max_grad_norm: float = 0.0     # 0 disables clipping (reference has none)
    # uniform label smoothing for seq2seq fine-tuning (T5/BART
    # convention, HF --label_smoothing_factor; train-time only — eval
    # loss stays plain CE). Composes with --fused_vocab_ce: the kernel
    # carries a running logit-sum next to its online-softmax stats.
    label_smoothing: float = 0.0
    # micro-batches averaged per optimizer update (1 = off): grows the
    # effective batch beyond HBM limits (e.g. BERT-large past bs 8/chip)
    gradient_accumulation_steps: int = 1
    steps_per_epoch: Optional[int] = None
    seed: int = 42
    # dropout-key PRNG. "rbg" uses the TPU's hardware RNG instruction —
    # threefry key-schedule math otherwise fuses into the weight-gradient
    # matmuls and throttles the MXU (~25% step time on BERT-base).
    # "threefry" remains for bit-exact cross-platform reproducibility.
    rng_impl: str = "rbg"

    # --- precision ---
    dtype: str = "bfloat16"        # compute dtype on TPU; tests override to float32
    param_dtype: str = "float32"

    # --- parallelism mesh (reference supports DP only; see SURVEY.md §2) ---
    dp: int = -1                   # -1: use all remaining devices on the data axis
    fsdp: int = 1
    ep: int = 1                    # expert parallel (MoE expert sharding)
    pp: int = 1                    # pipeline parallel (GPipe over stacked layers)
    tp: int = 1
    sp: int = 1                    # sequence/context parallel (ring attention)
    # outer data-parallel axis across slices connected by DCN rather
    # than ICI (multi-slice); blocks group whole slices/processes
    dcn_dp: int = 1
    # microbatches per pipeline round-trip (0 → = pp); more microbatches
    # shrink the fill/drain bubble: overhead ~ (pp-1)/(M+pp-1)
    pipeline_microbatches: int = 0

    # --- Mixture-of-Experts (models/moe.py; beyond-parity — the
    #     reference has no MoE). 0 = dense FFN everywhere. MoE weights
    #     are always fresh-initialized (HF BERT-family checkpoints have
    #     no experts); use with --from_scratch or for upcycling. ---
    num_experts: int = 0
    expert_top_k: int = 2
    moe_every: int = 2

    # --- kernels / memory ---
    # auto: flash (Pallas) on TPU, xla elsewhere, ring when sp > 1.
    # Measured on one v5e chip (BERT-base, seq 512, bf16): flash wins at
    # per-chip batch >= 16 and never loses, so it is the TPU default.
    attention_impl: str = "auto"   # auto | xla | flash (pallas) | ring
    remat: bool = False            # rematerialize encoder layers (FLOPs for HBM)
    # what remat saves at layer boundaries: "full" recomputes everything,
    # "dots" saves matmul outputs and recomputes only elementwise ops,
    # "dots_no_batch" also drops batch-dim matmul results (models/layers.py)
    remat_policy: str = "full"     # full | dots | dots_no_batch
    # Fused LM-head + CE (ops/pallas_vocab_ce.py): the [B,S,V] logits
    # never materialize in HBM. causal-lm only; opt-in (numerics match
    # the unfused path to fp32 roundoff, tests/test_vocab_ce.py).
    fused_vocab_ce: bool = False

    # --- QA doc-stride (HF run_qa semantics): contexts longer than the
    #     room left by the question become overlapping windows instead of
    #     being truncated — at training (independent rows) AND at the
    #     --eval_qa_samples EM/F1 eval (best-scoring span across each
    #     example's windows). 0 = truncate (reference-era behavior);
    #     HF's conventional value is 128. ---
    qa_doc_stride: int = 0

    # --- LoRA parameter-efficient fine-tuning (models/lora.py;
    #     beyond-parity — the reference trains every weight,
    #     train.py:117). rank 0 = off. With rank r > 0 the base model is
    #     frozen (no Adam state: the fp32 m/v mirrors that dominate HBM
    #     at the 16G ceiling vanish) and only A·B factors on the
    #     targeted kernels train; export merges them back into the
    #     checkpoint and also writes an adapter.safetensors sidecar. ---
    lora_rank: int = 0
    lora_alpha: float = 16.0
    lora_targets: str = "attention"   # attention | mlp | all | custom regex
    # fresh task heads stay fully trainable (PEFT modules_to_save
    # analogue) — freezing a random-init classifier would make the task
    # unlearnable; "" freezes them too (adapter-only, e.g. causal-lm
    # where the LM head is the tied embedding). The default lives HERE
    # (models/lora.py re-exports it as HEAD_REGEX_DEFAULT — config must
    # stay import-light, so the dependency points this way)
    lora_train_heads: str = LORA_HEAD_REGEX_DEFAULT

    # --- length bucketing (tf.data bucket_by_sequence_length capability;
    #     the reference pads everything to 512, train.py:80-83). 0 = off;
    #     N > 0 buckets token widths at multiples of N (e.g. 128 →
    #     128/256/384/512), one XLA compilation per bucket actually seen.
    #     Must stay a multiple of any ``sp`` sharding of the seq axis. ---
    bucket_multiple: int = 0

    # --- control flags (reference train.py:44-45, typed correctly here) ---
    do_train: bool = True
    do_eval: bool = True
    # per-epoch eval during fit (Keras validation_data shape): eval
    # metrics land in the training history as eval_loss/eval_accuracy
    eval_each_epoch: bool = False
    # HF load_best_model_at_end: snapshot the best epoch's params (by
    # --best_metric) to host and export THOSE instead of the final ones;
    # implies per-epoch eval
    keep_best: bool = False
    best_metric: str = "eval_loss"    # eval_loss | eval_accuracy
    # stop when --best_metric hasn't improved for N consecutive epochs
    # (0 = off; implies per-epoch eval). Composes with --keep_best: the
    # exported model is the best epoch's, not the stopping epoch's.
    early_stopping_patience: int = 0

    # --- checkpoint / resume (reference commented these out, train.py:136-137) ---
    checkpoint_dir: Optional[str] = None
    checkpoint_every_steps: int = 0      # 0: per-epoch only
    resume: bool = True                   # resume from latest checkpoint if present
    keep_checkpoints: int = 3
    async_checkpointing: bool = True      # overlap checkpoint writes with steps

    # --- replica-divergence detection (SURVEY.md §5.2): verify at every
    #     checkpoint boundary that parameter replicas across the data/seq
    #     mesh axes still agree (the consistency Horovod's broadcast only
    #     establishes at start, reference train.py:127-134). ---
    check_divergence: bool = True
    divergence_tol: float = 1e-6          # relative; replicas should be bit-equal

    # --- output contract (reference train.py:48-50) ---
    # accelerator-count env parity (reference SM_NUM_GPUS, train.py:50):
    # informational — the real device count comes from jax.devices();
    # scripts/train.py warns when the platform-declared count disagrees.
    num_chips: Optional[int] = field(
        default_factory=lambda: (
            int(v) if (v := _env("TPU_NUM_CHIPS", "SM_NUM_GPUS",
                                 default="")).isdigit() else None)
    )
    output_data_dir: str = field(
        default_factory=lambda: _env("TPU_OUTPUT_DATA_DIR", "SM_OUTPUT_DATA_DIR", default="/tmp/output")
    )
    model_dir: str = field(
        default_factory=lambda: _env("TPU_MODEL_DIR", "SM_MODEL_DIR", default="/tmp/model")
    )

    # --- observability ---
    log_every_steps: int = 10
    profile: bool = False          # capture a jax.profiler trace of a few steps
    profile_dir: str = "/tmp/profile"
    log_all_hosts: bool = False

    def __post_init__(self):
        if self.task not in ("seq-cls", "token-cls", "qa", "seq2seq",
                             "causal-lm", "mlm", "rtd"):
            raise ValueError(f"unknown task {self.task!r}")
        if self.dtype not in ("bfloat16", "float32", "float16"):
            raise ValueError(f"unknown dtype {self.dtype!r}")
        if self.rng_impl == "threefry":   # JAX's registry name for it
            self.rng_impl = "threefry2x32"
        if self.rng_impl not in ("rbg", "threefry2x32"):
            raise ValueError(f"unknown rng_impl {self.rng_impl!r}")
        if self.epochs < 0 or self.train_batch_size <= 0 or self.eval_batch_size <= 0:
            raise ValueError("epochs must be >= 0 and batch sizes positive")
        if self.gradient_accumulation_steps < 1:
            raise ValueError("gradient_accumulation_steps must be >= 1")
        if self.learning_rate <= 0:
            raise ValueError("learning_rate must be positive")
        if self.optimizer not in ("adamw", "adam", "adafactor", "lamb"):
            raise ValueError(f"unknown optimizer {self.optimizer!r}")
        if self.optimizer_state_dtype not in ("float32", "bfloat16"):
            raise ValueError(
                f"unknown optimizer_state_dtype {self.optimizer_state_dtype!r}")
        if (self.optimizer_state_dtype == "bfloat16"
                and self.optimizer not in ("adam", "adamw")):
            raise ValueError(
                "optimizer_state_dtype='bfloat16' supports adam/adamw only "
                "(adafactor is already sublinear-memory; lamb's trust "
                "ratio is untested with quantized moments)")
        if self.packed_sequences and self.task != "causal-lm":
            raise ValueError(
                "packed_sequences is a causal-lm pretraining layout "
                "(EOS-joined documents chunked into full rows); other "
                "tasks need per-example boundaries")
        if self.packed_sequences and self.streaming:
            raise ValueError(
                "packed_sequences does not combine with --streaming "
                "(the streaming tier tokenizes rows independently; "
                "packing needs the whole token stream) — pick one")
        if self.segment_packing and self.task not in ("causal-lm", "mlm"):
            raise ValueError(
                "segment_packing packs token-level examples behind "
                "segment ids (causal-lm / mlm); per-example-label tasks "
                f"cannot pack (got task={self.task!r})")
        if self.segment_packing and self.packed_sequences:
            raise ValueError(
                "segment_packing and packed_sequences are alternative "
                "packing layouts (per-example boundaries vs EOS-joined "
                "stream) — pick one")
        if self.segment_packing and self.streaming:
            raise ValueError(
                "segment_packing does not combine with --streaming "
                "(packing re-groups rows at build time; the streaming "
                "tier tokenizes per batch) — pick one")
        if self.segment_packing and self.bucket_multiple:
            raise ValueError(
                "segment_packing already eliminates pad waste; "
                "bucket_multiple would re-fragment packed rows — pick one")
        if self.streaming and self.span_corruption:
            raise ValueError(
                "--streaming does not implement span corruption (the "
                "streaming seq2seq tier encodes supervised source/target "
                "rows); drop --streaming for span-corruption pretraining")
        if self.optimizer == "adafactor" and self.weight_decay > 0:
            raise ValueError(
                "weight_decay with adafactor is not supported: optax "
                "applies it per-update after lr scaling (~1/lr stronger "
                "than AdamW's decoupled decay); use adamw or lamb")
        if self.optimizer == "adam" and self.weight_decay > 0:
            raise ValueError(
                "optimizer='adam' is plain coupled Adam (reference "
                "parity) and ignores weight_decay; use adamw")
        if self.lr_schedule not in ("linear", "cosine"):
            raise ValueError(f"unknown lr_schedule {self.lr_schedule!r}")
        if self.lr_schedule == "cosine" and self.warmup_ratio <= 0:
            raise ValueError(
                "lr_schedule='cosine' needs warmup_ratio > 0 (schedules "
                "only engage with a warmup+decay window; without it the "
                "lr is constant and the flag would be silently ignored)")
        for ax in ("fsdp", "ep", "pp", "tp", "sp", "dcn_dp"):
            if getattr(self, ax) <= 0:
                raise ValueError(f"mesh axis {ax} must be positive")
        if self.pipeline_microbatches < 0:
            raise ValueError("pipeline_microbatches must be >= 0")
        if self.pp > 1 and self.num_experts:
            raise ValueError("pp > 1 cannot combine with num_experts (MoE)")
        if self.num_experts < 0 or self.expert_top_k < 1 or self.moe_every < 1:
            raise ValueError("num_experts >= 0, expert_top_k >= 1, moe_every >= 1")
        if self.ep > 1 and self.num_experts == 0:
            raise ValueError("ep > 1 requires num_experts > 0 (MoE model)")
        if not 0.0 <= self.label_smoothing < 1.0:
            raise ValueError("label_smoothing must be in [0, 1)")
        if self.label_smoothing > 0 and self.task != "seq2seq":
            raise ValueError(
                "label_smoothing is implemented for task='seq2seq' (the "
                "T5/BART fine-tuning convention); other tasks would "
                "silently ignore it")
        if self.best_metric not in ("eval_loss", "eval_accuracy"):
            raise ValueError(
                f"unknown best_metric {self.best_metric!r} "
                "(eval_loss | eval_accuracy)")
        if self.early_stopping_patience < 0:
            raise ValueError("early_stopping_patience must be >= 0")
        if self.early_stopping_patience > 0:
            self.eval_each_epoch = True
        if self.keep_best and not self.do_eval:
            raise ValueError("keep_best needs do_eval=true (it selects "
                             "by eval metric)")
        if self.early_stopping_patience > 0 and not self.do_eval:
            raise ValueError("early_stopping_patience needs do_eval=true "
                             "(it watches an eval metric)")
        if self.keep_best:
            self.eval_each_epoch = True
        if self.remat_policy not in ("full", "dots", "dots_no_batch"):
            raise ValueError(f"unknown remat_policy {self.remat_policy!r}")
        if self.qa_doc_stride < 0:
            raise ValueError("qa_doc_stride must be >= 0 (0 disables)")
        if 0 < self.max_seq_length - 3 <= self.qa_doc_stride:
            # stride is the OVERLAP between windows: when it meets or
            # exceeds the best-case window room (empty question), every
            # example degenerates to 1-token steps — up to one feature
            # per context token, a quiet memory/time blowup
            raise ValueError(
                f"qa_doc_stride={self.qa_doc_stride} >= "
                f"max_seq_length-3={self.max_seq_length - 3} (the maximum "
                "context window room): windows would step 1 token at a "
                "time; lower --qa_doc_stride or raise --max_seq_length")
        if self.lora_rank < 0:
            raise ValueError("lora_rank must be >= 0 (0 disables LoRA)")
        if self.lora_rank > 0 and self.lora_alpha <= 0:
            raise ValueError("lora_alpha must be positive")
        # lora_rank > 0 composes with gradient accumulation: the trainer
        # wraps multi_transform AROUND the MultiSteps'd optimizer, so the
        # accumulator only ever sees the trainable (adapter+head) subtree
        # — MaskedNode placeholders carry no leaves and accumulate
        # nothing (parity-tested in tests/test_lora.py)
        if self.num_experts and self.num_experts % self.ep:
            raise ValueError(
                f"num_experts={self.num_experts} must divide over ep={self.ep}")
        if self.num_experts and self.expert_top_k > self.num_experts:
            raise ValueError("expert_top_k cannot exceed num_experts")
        if self.bucket_multiple < 0:
            raise ValueError("bucket_multiple must be >= 0")
        if self.bucket_multiple and self.sp > 1 and self.bucket_multiple % self.sp:
            raise ValueError("bucket_multiple must divide evenly over sp shards")
        if self.attention_impl not in ("auto", "xla", "flash", "ring"):
            raise ValueError(f"unknown attention_impl {self.attention_impl!r}")
        if self.attention_impl == "flash" and self.sp > 1:
            raise ValueError(
                "attention_impl='flash' cannot run over a sequence-sharded "
                "axis (sp>1); use 'ring' or 'auto'")

    def resolve_attention_impl(self, platform: str) -> str:
        """Single source of truth for the attention kernel choice.

        A seq mesh axis (sp > 1) forces ring attention — xla/flash compute
        per-shard attention over a sharded seq axis, which is wrong
        (flash+sp is already rejected at construction). ``auto`` then
        picks flash (Pallas) on real TPU and xla elsewhere (on CPU the
        Pallas kernels would run in slow interpret mode)."""
        if self.sp > 1:
            return "ring"
        if self.attention_impl != "auto":
            return self.attention_impl
        return "flash" if platform == "tpu" else "xla"

    def bucket_sizes(self, max_len: int) -> Optional[list[int]]:
        """The length-bucket width schedule ``bucket_multiple`` implies:
        multiples of it up to ``max_len`` (validated sp-divisible in
        ``__post_init__``). None when bucketing is off. Read by
        ``scripts/train.py``."""
        if not self.bucket_multiple:
            return None
        return list(range(self.bucket_multiple, max_len + 1,
                          self.bucket_multiple))

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    def to_json(self) -> str:
        return json.dumps(self.to_dict(), indent=2, sort_keys=True)

    @classmethod
    def from_dict(cls, d: dict) -> "TrainConfig":
        known = {f.name for f in fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in known})


def _add_field_arg(parser: argparse.ArgumentParser, f: dataclasses.Field) -> None:
    name = "--" + f.name
    if f.type in ("bool", bool):
        parser.add_argument(name, type=_parse_bool, default=None)
    elif f.type in ("int", int):
        parser.add_argument(name, type=int, default=None)
    elif f.type in ("float", float):
        parser.add_argument(name, type=float, default=None)
    elif f.type in ("Optional[int]",):
        parser.add_argument(name, type=int, default=None)
    else:
        parser.add_argument(name, type=str, default=None)


def parse_args(argv: Optional[list[str]] = None) -> TrainConfig:
    """Build a TrainConfig from CLI args layered over env/defaults.

    Hyperparameters arrive as ``--key value`` strings exactly as the
    SageMaker platform serializes them (reference ``launch.py:51`` →
    ``scripts/train.py:39-46``); every value is validated and coerced to
    its typed field. Unknown args are ignored.
    """
    parser = argparse.ArgumentParser(allow_abbrev=False)
    for f in fields(TrainConfig):
        _add_field_arg(parser, f)
    ns, _unknown = parser.parse_known_args(argv)
    overrides = {k: v for k, v in vars(ns).items() if v is not None}
    base = TrainConfig()
    merged = {**base.to_dict(), **overrides}
    return TrainConfig.from_dict(merged)
