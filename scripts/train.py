"""Distributed fine-tuning entry point.

Parity with reference ``scripts/train.py`` (the multi-worker Horovod/SMDDP
trainer): hyperparameters arrive as CLI args (platform-serialized, with
``SM_*``/``TPU_*`` env defaults for the output dirs), the model is
fine-tuned data-parallel with world-size LR scaling, per-epoch history +
``train_runtime`` land in ``train_results.txt``, eval metrics in
``eval_results.txt``, and model + tokenizer are exported in HF layout to
``model_dir``.

Unlike the reference there is no separate single-node script needed:
distribution is ambient in the mesh (1 chip, 8 chips, multi-host slice —
same code; ``scripts/single_node_train.py`` is a thin alias kept for
launcher parity). Beyond the reference: checkpoint/resume
(the reference commented it out), per-host dataset sharding (the
reference trains on K× data with K workers), typed config (its
``--learning_rate`` was a str), host-0-gated writes.
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

from huggingface_sagemaker_tensorflow_distributed_tpu.config import TrainConfig, parse_args
from huggingface_sagemaker_tensorflow_distributed_tpu.data import (
    ArrayDataset,
    ShardedBatcher,
    load_tokenizer,
)
from huggingface_sagemaker_tensorflow_distributed_tpu.data.sources import (
    load_qa,
    load_seq2seq,
    load_text_classification,
    load_token_classification,
)
from huggingface_sagemaker_tensorflow_distributed_tpu.models import auto as auto_models
from huggingface_sagemaker_tensorflow_distributed_tpu.parallel import (
    MeshConfig,
    build_mesh,
    device_memory_peaks,
    enable_compilation_cache,
    initialize_distributed,
    require_accelerator,
)
from huggingface_sagemaker_tensorflow_distributed_tpu.train import Trainer
from huggingface_sagemaker_tensorflow_distributed_tpu.train.checkpoint import Checkpointer
from huggingface_sagemaker_tensorflow_distributed_tpu.utils import (
    get_logger,
    setup_logging,
    write_results_file,
)

import jax.numpy as jnp

_DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32, "float16": jnp.float16}


def _check_num_labels(labels, num_labels: int, task: str) -> None:
    """Out-of-range labels would be silently clamped by the gather inside
    the jitted CE loss — fail loudly at data-build time instead."""
    top = max((l for l in labels if l >= 0), default=0)
    if top >= num_labels:
        raise ValueError(
            f"{task}: dataset contains label {top} but --num_labels is "
            f"{num_labels}; pass --num_labels {top + 1} (conll2003 needs 9)")


def build_streaming_dataset(config: TrainConfig, tokenizer, split: str,
                            max_len: int, max_samples, model_config=None):
    """--streaming true: corpus stays on disk, tokenized per batch
    (fixes the reference's materialize-everything quirk, reference
    ``scripts/train.py:80-83``). Sources: ``dataset_path/{split}.jsonl``
    or ``.txt``; the synthetic tier writes its corpus to a cached file
    once so the path is identical to a real on-disk corpus."""
    from huggingface_sagemaker_tensorflow_distributed_tpu.data.streaming import (
        LineCorpus,
        StreamingTextDataset,
    )

    if config.dataset_path:
        base = os.path.join(config.dataset_path, split)
        path = next((base + ext for ext in (".jsonl", ".txt")
                     if os.path.exists(base + ext)), None)
        if path is None:
            raise ValueError(f"--streaming: no {base}.jsonl or .txt")
    elif config.dataset == "synthetic":
        import json as _json
        import tempfile

        n = max_samples or 2000
        path = os.path.join(
            tempfile.gettempdir(),
            f"stream_synth_{config.task}_{split}_{n}_{config.seed}.jsonl")
        if not os.path.exists(path):
            if config.task == "seq2seq":
                sources, targets = load_seq2seq(
                    "synthetic", split, max_samples=n, seed=config.seed)
                rows = [{"source": s, "target": t}
                        for s, t in zip(sources, targets)]
            else:
                texts, labels = load_text_classification(
                    "synthetic", split, max_samples=n, seed=config.seed)
                rows = [{"text": t, "label": l}
                        for t, l in zip(texts, labels)]
            # per-process unique tmp + atomic replace: multiple local
            # hosts may race to build the same (deterministic) cache file
            fd, tmp = tempfile.mkstemp(dir=os.path.dirname(path),
                                       suffix=".tmp")
            with os.fdopen(fd, "w") as f:
                for rec in rows:
                    f.write(_json.dumps(rec) + "\n")
            os.replace(tmp, path)
    else:
        raise ValueError(
            "--streaming needs --dataset_path (train.jsonl/.txt) or "
            "--dataset synthetic")
    corpus = LineCorpus(path, max_rows=max_samples)
    seq2seq_kwargs = None
    if config.task == "seq2seq":
        seq2seq_kwargs = dict(
            max_target_length=config.max_target_length,
            decoder_start_token_id=getattr(model_config,
                                           "decoder_start_token_id", 0),
            pad_token_id=getattr(model_config, "pad_token_id", 0),
            eos_token_id=getattr(model_config, "eos_token_id", 1))
    return StreamingTextDataset(corpus, tokenizer, task=config.task,
                                max_length=max_len, seed=config.seed,
                                num_labels=config.num_labels
                                if config.task == "seq-cls" else None,
                                seq2seq_kwargs=seq2seq_kwargs)


def build_dataset(config: TrainConfig, tokenizer, split: str, max_len: int,
                  max_samples, model_config=None) -> ArrayDataset:
    """Task-specific load+tokenize: seq-cls (reference parity), token-cls
    (CoNLL), extractive QA (SQuAD), seq2seq (CNN-DM) — each with a
    synthetic offline tier."""
    kw = dict(dataset_path=config.dataset_path, max_samples=max_samples,
              seed=config.seed)
    if config.streaming and split == "train":
        return build_streaming_dataset(config, tokenizer, split, max_len,
                                       max_samples, model_config)
    if config.task == "seq-cls":
        texts, labels = load_text_classification(config.dataset, split, **kw)
        _check_num_labels(labels, config.num_labels, config.task)
        return ArrayDataset.from_texts(tokenizer, texts, labels, max_len)
    if config.task == "causal-lm":
        # any text source works as an LM corpus; classification labels
        # are simply ignored
        texts, _ = load_text_classification(config.dataset, split, **kw)
        ds = ArrayDataset.from_lm_texts(
            tokenizer, texts, max_len,
            packed=config.packed_sequences,
            eos_token_id=getattr(model_config, "eos_token_id", None))
        if config.segment_packing:
            # token packing with per-example boundaries: segment ids +
            # restarting positions keep attention and loss per-example
            # exact (vs packed_sequences' cross-document attention)
            ds = ds.pack(max_len, causal=True)
        return ds
    if config.task == "mlm":
        texts, _ = load_text_classification(config.dataset, split, **kw)
        ds = ArrayDataset.from_mlm_texts(
            tokenizer, texts, max_len, seed=config.seed,
            static_masking=config.mlm_static_masking)
        if config.segment_packing:
            # MlmDataset.pack enforces the static-masking requirement;
            # re-raise with the CLI flag spelled out
            if not config.mlm_static_masking:
                raise ValueError(
                    "--segment_packing with task=mlm requires "
                    "--mlm_static_masking true (packing freezes the "
                    "masking draw at build time)")
            ds = ds.pack(max_len)
        return ds
    if config.task == "rtd":
        texts, _ = load_text_classification(config.dataset, split, **kw)
        return ArrayDataset.from_rtd_texts(tokenizer, texts, max_len,
                                           seed=config.seed)
    if config.task == "token-cls":
        sents, tags = load_token_classification(config.dataset, split, **kw)
        _check_num_labels([t for ts in tags for t in ts], config.num_labels,
                          config.task)
        return ArrayDataset.from_token_classification(tokenizer, sents, tags, max_len)
    if config.task == "qa":
        questions, contexts, starts, answers = load_qa(config.dataset, split, **kw)
        return ArrayDataset.from_qa(tokenizer, questions, contexts, starts,
                                    answers, max_len,
                                    doc_stride=config.qa_doc_stride)
    if config.task == "seq2seq" and config.span_corruption:
        try:
            texts, _ = load_text_classification(config.dataset, split, **kw)
        except ValueError:
            # seq2seq-registry datasets (cnn_dailymail, ...) work as a
            # plain text corpus: corrupt the source documents
            texts, _ = load_seq2seq(config.dataset, split, **kw)
        # a corrupted 512-token source needs ~0.2*len target tokens
        # (spans + sentinels + final sentinel); the task default of 64
        # would truncate spans away silently
        needed = int(max_len * 0.2) + 4
        tgt_len = max(config.max_target_length, needed)
        if tgt_len != config.max_target_length:
            get_logger("train").info(
                "span_corruption: raising max_target_length %d → %d to fit "
                "the corrupted spans", config.max_target_length, tgt_len)
        return ArrayDataset.from_span_corruption_texts(
            tokenizer, texts, max_source_length=max_len,
            max_target_length=tgt_len,
            decoder_start_token_id=getattr(model_config,
                                           "decoder_start_token_id", 0),
            pad_token_id=getattr(model_config, "pad_token_id", 0),
            eos_token_id=getattr(model_config, "eos_token_id", 1),
            seed=config.seed)
    if config.task == "seq2seq":
        sources, targets = load_seq2seq(config.dataset, split, **kw)
        return ArrayDataset.from_seq2seq(
            tokenizer, sources, targets, max_source_length=max_len,
            max_target_length=config.max_target_length,
            decoder_start_token_id=getattr(model_config,
                                           "decoder_start_token_id", 0),
            pad_token_id=getattr(model_config, "pad_token_id", 0),
            eos_token_id=getattr(model_config, "eos_token_id", 1))
    raise ValueError(f"no data path for task {config.task!r}")


def main(argv=None) -> dict:
    config = parse_args(argv)
    process_index, process_count = initialize_distributed()
    device = require_accelerator()
    cache_dir = enable_compilation_cache()
    setup_logging(process_index=process_index, all_hosts=config.log_all_hosts)
    logger = get_logger("train")
    logger.info("config: %s", config.to_json())
    logger.info("process %d/%d, backend %s, compile cache %s", process_index,
                process_count, device, cache_dir)
    # per-host contract, like the reference's SM_NUM_GPUS (train.py:50) —
    # so compare against this host's devices, not the global mesh
    n_local = len(jax.local_devices())
    if config.num_chips is not None and config.num_chips != n_local:
        logger.warning(
            "platform declared %d accelerators (TPU_NUM_CHIPS/SM_NUM_GPUS) "
            "but %d local JAX devices are visible; using the visible devices",
            config.num_chips, n_local)

    mesh = build_mesh(MeshConfig(dp=config.dp, fsdp=config.fsdp,
                                 ep=config.ep, pp=config.pp,
                                 tp=config.tp, sp=config.sp,
                                 dcn_dp=config.dcn_dp))
    logger.info("mesh: %s", dict(mesh.shape))

    # --- model + tokenizer (reference train.py:69,117) ---
    attention_impl = config.resolve_attention_impl(jax.devices()[0].platform)
    moe_overrides = {}
    if config.num_experts:
        moe_overrides = dict(num_experts=config.num_experts,
                             expert_top_k=config.expert_top_k,
                             moe_every=config.moe_every)
    if config.pp > 1:
        moe_overrides.update(
            pipeline_stages=config.pp,
            pipeline_microbatches=config.pipeline_microbatches)
    model, params, family, model_config = auto_models.from_pretrained(
        config.model_name_or_path,
        task=config.task,
        num_labels=config.num_labels,
        dtype=_DTYPES[config.dtype],
        param_dtype=_DTYPES[config.param_dtype],
        seed=config.seed,
        from_scratch=config.from_scratch,
        attention_impl=attention_impl,
        remat=config.remat,
        remat_policy=config.remat_policy,
        **moe_overrides,
    )
    if config.num_experts:
        logger.info("MoE: %d experts (top-%d) every %d layers, ep=%d",
                    config.num_experts, config.expert_top_k,
                    config.moe_every, config.ep)
    if attention_impl == "ring":
        if family == "t5":
            logger.info(
                "sp=%d: ring attention on the T5 encoder (relative bias "
                "re-tiled per ring step); decoder/cross attention run XLA "
                "with seq-sharded activations", config.sp)
        else:
            logger.info("sp=%d: ring attention selected", config.sp)
    if config.segment_packing:
        # only models that grew the segment_ids/position_ids kwargs can
        # consume packed batches — anything else would TypeError at
        # trace time with an opaque flax message
        if family not in ("gpt2", "bert"):
            raise ValueError(
                "--segment_packing needs a model wired for segment_ids/"
                "position_ids (gpt2 causal-lm, bert mlm); "
                f"got family {family!r}")
        if attention_impl == "ring":
            raise ValueError(
                "--segment_packing builds a [B,1,S,S] block-diagonal "
                "mask, which ring attention (sp>1) cannot shard over the "
                "seq axis — drop --sp or --segment_packing")
        if attention_impl == "flash":
            logger.warning(
                "--segment_packing builds a [B,1,S,S] block-diagonal "
                "mask, which the Pallas flash kernel treats as a general "
                "mask and falls back to XLA attention — long-sequence "
                "memory is O(S^2) on this run, not O(S)")
    tokenizer = load_tokenizer(config.model_name_or_path,
                               vocab_size=model_config.vocab_size)

    # --- data (reference train.py:72-100), per-host sharded, task-aware ---
    max_len = min(config.max_seq_length,
                  getattr(model_config, "max_position_embeddings",
                          config.max_seq_length))
    train_ds = build_dataset(config, tokenizer, "train", max_len,
                             config.max_train_samples, model_config)
    eval_ds = build_dataset(config, tokenizer, "test", max_len,
                            config.max_eval_samples, model_config)

    # Global batch = per-replica batch × data-parallel replicas (reference
    # semantics at train.py:143-144). tp/sp devices within a replica do
    # NOT multiply the batch — they cooperate on the same examples.
    from huggingface_sagemaker_tensorflow_distributed_tpu.parallel.mesh import (
        data_parallel_size,
    )
    dp_size = data_parallel_size(mesh)
    global_train_batch = config.train_batch_size * dp_size
    global_eval_batch = config.eval_batch_size * dp_size
    buckets = config.bucket_sizes(max_len)
    if buckets:
        logger.info("length bucketing at widths %s", buckets)
    train_batcher = ShardedBatcher(train_ds, global_train_batch, mesh,
                                   shuffle=True, seed=config.seed,
                                   bucket_sizes=buckets)
    eval_batcher = ShardedBatcher(eval_ds, global_eval_batch, mesh,
                                  shuffle=False, drop_remainder=False,
                                  bucket_sizes=buckets)

    total_steps = train_batcher.steps_per_epoch() * config.epochs
    trainer = Trainer(config, model, params, mesh, total_steps=total_steps)

    # --- checkpoint/resume (capability the reference commented out) ---
    checkpointer = None
    start_epoch = 0
    start_step_in_epoch = 0
    if config.checkpoint_dir:
        checkpointer = Checkpointer(config.checkpoint_dir,
                                    max_to_keep=config.keep_checkpoints,
                                    async_save=config.async_checkpointing)
        if config.resume:
            restored = checkpointer.restore(trainer.state)
            if restored is not None:
                trainer.state, start_epoch, start_step_in_epoch = restored
                logger.info("resuming from epoch %d (step-in-epoch %d)",
                            start_epoch, start_step_in_epoch)
                if config.keep_best or config.early_stopping_patience:
                    logger.warning(
                        "--keep_best/--early_stopping_patience across a "
                        "resume: best-metric and patience tracking live "
                        "in host RAM, not the checkpoint — both restart "
                        "at this epoch (earlier epochs can no longer "
                        "win, and the patience budget is fresh)")

    results: dict = {}
    try:
        if config.do_train:
            logger.info("*** Train ***")
            history = trainer.fit(
                train_batcher, checkpointer=checkpointer,
                start_epoch=start_epoch,
                start_step_in_epoch=start_step_in_epoch,
                eval_batcher=eval_batcher if config.eval_each_epoch
                else None)
            if config.keep_best and trainer.best_epoch is not None:
                logger.info("exporting best epoch %d (%s = %.4f)",
                            trainer.best_epoch, config.best_metric,
                            trainer._best_metric)
            # what the run was measured on rides in the results file
            history.update(device, peak_bytes_in_use=device_memory_peaks())
            trainer.write_train_results(history)
            results["train"] = history

        if config.do_eval:
            logger.info("*** Evaluate ***")
            eval_results = trainer.evaluate(eval_batcher)
            if config.task == "seq2seq" and config.eval_rouge_samples:
                import numpy as np

                from huggingface_sagemaker_tensorflow_distributed_tpu.models.generate import (
                    generate,
                )
                from huggingface_sagemaker_tensorflow_distributed_tpu.utils.metrics import (
                    rouge_l,
                )

                n = min(config.eval_rouge_samples, len(eval_ds))
                cols = eval_ds[np.arange(n)]
                out = generate(model, trainer.export_params,
                               cols["input_ids"], cols["attention_mask"],
                               max_new_tokens=config.max_target_length)
                preds = [tokenizer.decode(r) for r in np.asarray(out)]
                refs = [tokenizer.decode(r[r != -100])
                        for r in cols["labels"]]
                eval_results.update(rouge_l(preds, refs))
            if config.task == "qa" and config.eval_qa_samples:
                # answer-TEXT exact-match/F1 (the metric SQuAD results are
                # quoted in), decoded from span logits via char offsets —
                # span-position accuracy alone under-reports whenever a
                # different token span yields the same normalized text
                import numpy as np

                from huggingface_sagemaker_tensorflow_distributed_tpu.utils.metrics import (
                    best_windowed_answers,
                    extract_answer_spans,
                    squad_em_f1,
                )

                questions, contexts, starts, answers = load_qa(
                    config.dataset, "test", dataset_path=config.dataset_path,
                    max_samples=config.eval_qa_samples, seed=config.seed)
                enc = tokenizer.encode_qa(questions, contexts, starts,
                                          answers, max_length=max_len,
                                          return_offsets=True,
                                          doc_stride=config.qa_doc_stride)
                # with doc-stride each input yields several window
                # features; predictions aggregate per example below
                ex_ids = enc["example_ids"]
                feat_ctx = np.asarray(contexts)[ex_ids]
                texts_scores: list = []
                bs = global_eval_batch
                n_feat = enc["input_ids"].shape[0]
                # hoisted: export_params re-merges LoRA adapters on every
                # read — do it once, not once per eval batch
                eval_params = trainer.export_params
                for lo in range(0, n_feat, bs):
                    sl = slice(lo, min(lo + bs, n_feat))
                    s_log, e_log = model.apply(
                        {"params": eval_params},
                        jnp.asarray(enc["input_ids"][sl]),
                        jnp.asarray(enc["attention_mask"][sl]),
                        token_type_ids=jnp.asarray(enc["token_type_ids"][sl])
                        if "token_type_ids" in enc else None,
                        deterministic=True)
                    texts_scores.extend(extract_answer_spans(
                        s_log, e_log, enc["offset_starts"][sl],
                        enc["offset_ends"][sl], feat_ctx[sl],
                        with_scores=True))
                preds = best_windowed_answers(
                    [t for t, _ in texts_scores],
                    [sc for _, sc in texts_scores], ex_ids, len(questions))
                em_f1 = squad_em_f1(preds, list(answers))
                eval_results["eval_exact_match"] = em_f1["exact_match"]
                eval_results["eval_f1"] = em_f1["f1"]
            trainer.write_eval_results(eval_results)
            results["eval"] = eval_results

        # --- terminal export, HF layout (reference train.py:182-183) ---
        auto_models.save_pretrained(config.model_dir, trainer.export_params,
                                    family, model_config)
        adapters = None
        if config.lora_rank > 0:
            adapters = trainer.state.params["lora"]
            if jax.process_count() > 1:
                # stacked (pipelined) adapters can shard across hosts —
                # gather collectively BEFORE the host-0 gate, same
                # discipline as save_pretrained
                from jax.experimental import multihost_utils

                adapters = multihost_utils.process_allgather(adapters,
                                                             tiled=True)
        if jax.process_index() == 0:
            tokenizer.save_pretrained(config.model_dir)
            if adapters is not None:
                # adapter sidecar next to the merged export: deployment
                # can ship megabytes instead of the full model
                from huggingface_sagemaker_tensorflow_distributed_tpu.models.lora import (
                    save_adapters,
                )
                save_adapters(
                    os.path.join(config.model_dir, "adapter"),
                    adapters, rank=config.lora_rank,
                    alpha=config.lora_alpha, targets=config.lora_targets)
    finally:
        # commits any in-flight ASYNC checkpoint write even when fit/eval
        # raise — a crash after "save started" must not lose the checkpoint
        if checkpointer is not None:
            checkpointer.close()
    # the program maps (obs/programs.py) while the trainer, whose steps
    # they describe, is still here; nothing without a telemetry directory
    from huggingface_sagemaker_tensorflow_distributed_tpu import obs

    obs.flush()
    return results


if __name__ == "__main__":
    # real CLI runs default their telemetry into the output dir (the
    # <out_dir>/telemetry/{events.jsonl,trace.json} layout, README
    # "Telemetry"); in-process callers (tests) opt in via
    # HSTD_TELEMETRY_DIR or obs.configure instead, so importing/calling
    # main() never writes files as a side effect
    if not os.environ.get("HSTD_TELEMETRY_DIR", "").strip():
        from huggingface_sagemaker_tensorflow_distributed_tpu import obs

        _out = os.environ.get("TPU_OUTPUT_DATA_DIR",
                              os.environ.get("SM_OUTPUT_DATA_DIR", ""))
        if _out:
            obs.configure(out_dir=os.path.join(_out, "telemetry"))
    main(sys.argv[1:])
