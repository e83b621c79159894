"""Inference entry point: load an exported checkpoint and predict.

Completes the model-surface parity with the reference's HF ecosystem
(the reference's model objects carry ``pipeline``-style inference via
``transformers``; the repo itself only fine-tunes — reference
``scripts/train.py:145,170``). One jitted forward (or the cached
generation loop) per invocation:

  python scripts/predict.py --model_dir /path/to/export --task seq-cls \
      --text "a great movie"
  python scripts/predict.py --model_dir ... --task qa \
      --text "who wrote it?" --context "it was written by Ada."
  python scripts/predict.py --model_dir ... --task seq2seq \
      --text "summarize: ..." --max_new_tokens 48 --num_beams 4
  python scripts/predict.py --model_dir ... --task causal-lm \
      --text "once upon a time" --temperature 0.8 --top_p 0.9
  python scripts/predict.py --model_dir ... --task mlm \
      --text "the capital of france is [MASK]"

Each input line (from ``--text``/``--context`` or ``--input_file``
jsonl with {"text": ..., "context"?: ...}) produces ONE JSON line on
stdout.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from huggingface_sagemaker_tensorflow_distributed_tpu.data import load_tokenizer
from huggingface_sagemaker_tensorflow_distributed_tpu.models import auto as auto_models
from huggingface_sagemaker_tensorflow_distributed_tpu.parallel import (
    enable_compilation_cache,
    require_accelerator,
)


def _encode_mlm_with_mask(tokenizer, texts, max_length, mask_id):
    """Encode texts containing literal "[MASK]" markers for tokenizers
    that don't recognize the token inline: tokenize the segments around
    each marker and splice the mask id between them."""
    cls_id = getattr(tokenizer, "cls_token_id", None)
    sep_id = getattr(tokenizer, "sep_token_id", None)
    pad_id = getattr(tokenizer, "pad_token_id", 0)
    rows = []
    for text in texts:
        row = [cls_id] if cls_id is not None else []
        parts = text.split("[MASK]")
        for i, part in enumerate(parts):
            if part.strip():
                seg = tokenizer([part], add_special_tokens=False,
                                max_length=max_length)
                am = np.asarray(seg["attention_mask"][0])
                row += [int(x) for x in np.asarray(seg["input_ids"][0])[am > 0]]
            if i < len(parts) - 1:
                row.append(int(mask_id))
        if sep_id is not None:
            row.append(sep_id)
        if int(mask_id) not in row[:max_length] and int(mask_id) in row:
            print(f"warning: [MASK] in {text[:40]!r} fell past "
                  f"--max_seq_length {max_length} and was truncated away",
                  file=sys.stderr)
        rows.append(row[:max_length])
    width = max(len(r) for r in rows)
    ids = np.full((len(rows), width), pad_id, np.int32)
    am = np.zeros((len(rows), width), np.int32)
    for r, row in enumerate(rows):
        ids[r, : len(row)] = row
        am[r, : len(row)] = 1
    return {"input_ids": ids, "attention_mask": am}


def _encode(tokenizer, texts, contexts, max_length):
    # 'longest' keeps the jitted width at the actual batch length
    if contexts is not None:
        return tokenizer(texts, text_pairs=contexts, max_length=max_length,
                         padding="longest")
    return tokenizer(texts, max_length=max_length, padding="longest")


def predict(args) -> list[dict]:
    overrides = {}
    if getattr(args, "kv_cache", "fp") != "fp":
        if args.task != "causal-lm":
            raise SystemExit("--kv_cache int8 is a decode-cache knob "
                             "(Llama family + GPT-2); use --task "
                             "causal-lm")
        overrides["kv_cache_dtype"] = args.kv_cache
    model, params, family, config = auto_models.from_pretrained(
        args.model_dir, task=args.task, num_labels=args.num_labels,
        **overrides)
    tokenizer = load_tokenizer(args.model_dir, vocab_size=config.vocab_size)

    if getattr(args, "adapter", None):
        # LoRA sidecar deployment: merge adapter.safetensors onto the
        # base checkpoint at load (the alternative to shipping the
        # merged export scripts/train.py writes)
        from huggingface_sagemaker_tensorflow_distributed_tpu.models.lora import (
            load_adapters,
            lora_scaling,
            merge_lora,
        )

        lora, meta = load_adapters(args.adapter)
        params = merge_lora(params, lora,
                            lora_scaling(meta["lora_rank"],
                                         meta["lora_alpha"]))
        print(f"adapter: r={meta['lora_rank']} alpha={meta['lora_alpha']} "
              f"targets={meta['lora_targets']} merged", file=sys.stderr)

    if getattr(args, "quantize", "none") == "int8":
        # int8 weight-only decode (models/quant.py): HBM-bound decode
        # reads 1/4 the kernel bytes; compute stays in the model dtype
        if args.task not in ("causal-lm", "seq2seq"):
            raise SystemExit("--quantize int8 covers the generation tasks "
                             "(--task causal-lm or seq2seq)")
        from huggingface_sagemaker_tensorflow_distributed_tpu.models.quant import (
            quantize_for_generation,
        )
        model, params, stats = quantize_for_generation(model, params)
        print(f"int8: {stats['kernels_quantized']} kernels, "
              f"{stats['bytes_before']/1e6:.1f} -> "
              f"{stats['bytes_after']/1e6:.1f} MB", file=sys.stderr)

    if args.input_file:
        rows = [json.loads(l) for l in open(args.input_file) if l.strip()]
        texts = [r["text"] for r in rows]
        # context is per-row optional; rows without one get an empty pair
        contexts = ([r.get("context", "") for r in rows]
                    if any("context" in r for r in rows) else None)
    else:
        texts = [args.text]
        contexts = [args.context] if args.context else None

    max_len = min(args.max_seq_length,
                  getattr(config, "max_position_embeddings", args.max_seq_length))
    qa_offsets = None
    if (args.task == "qa" and contexts is not None
            and hasattr(tokenizer, "encode_qa")):
        # QA gets the eval-metric encoding: only_second truncation plus
        # char offsets, so the answer decodes by slicing the ORIGINAL
        # context (exact surface text) with the joint span search
        enc = dict(tokenizer.encode_qa(texts, contexts, max_length=max_len,
                                       return_offsets=True,
                                       doc_stride=args.doc_stride))
        # encode_qa pads to max_length; trim every column to the longest
        # real row (the 'longest' contract of _encode) so the jitted
        # width tracks the batch
        width = max(int(np.asarray(enc["attention_mask"]).sum(1).max()), 1)
        enc = {k: v[:, :width] if getattr(v, "ndim", 1) == 2 else v
               for k, v in enc.items()}
        qa_offsets = (enc["offset_starts"], enc["offset_ends"])
        qa_example_ids = enc.get("example_ids")
    else:
        enc = _encode(tokenizer, texts, contexts, max_len)
    ids = jnp.asarray(enc["input_ids"])
    mask = jnp.asarray(enc["attention_mask"])
    token_types = (jnp.asarray(enc["token_type_ids"])
                   if "token_type_ids" in enc else None)

    results: list[dict] = []
    if args.task in ("seq2seq", "causal-lm"):
        from huggingface_sagemaker_tensorflow_distributed_tpu.models.generate import (
            beam_search_generate,
            generate,
            generate_causal,
        )

        if (getattr(args, "self_speculate_layers", 0)
                and args.task != "causal-lm"):
            raise SystemExit("--self_speculate_layers (layer-skip "
                             "self-speculation) supports --task "
                             "causal-lm only; seq2seq speculation needs "
                             "a separate --draft_dir checkpoint")
        if getattr(args, "prefill_chunk", 0):
            if args.task != "causal-lm":
                raise SystemExit("--prefill_chunk supports --task "
                                 "causal-lm only")
            if (getattr(args, "draft_dir", None)
                    or getattr(args, "self_speculate_layers", 0)):
                raise SystemExit("--prefill_chunk cannot combine with "
                                 "speculative decoding (its prefill is "
                                 "not chunked)")
            if args.num_beams > 1:
                raise SystemExit("--prefill_chunk cannot combine with "
                                 "--num_beams (beam prefill is not "
                                 "chunked)")
        if args.task == "seq2seq":
            if getattr(args, "draft_dir", None):
                from huggingface_sagemaker_tensorflow_distributed_tpu.models.generate import (
                    generate_speculative_seq2seq,
                )

                if args.num_beams > 1 or args.top_k or args.top_p:
                    raise SystemExit(
                        "--draft_dir for seq2seq supports greedy and "
                        "plain --temperature sampling only (no beams, "
                        "no top-k/top-p)")
                draft_model, draft_params, _, _ = \
                    auto_models.from_pretrained(args.draft_dir,
                                                task="seq2seq")
                out = generate_speculative_seq2seq(
                    model, params, draft_model, draft_params, ids, mask,
                    max_new_tokens=args.max_new_tokens,
                    speculate_k=args.speculate_k,
                    temperature=args.temperature, seed=args.seed)
            elif args.num_beams > 1:
                out = beam_search_generate(model, params, ids, mask,
                                           num_beams=args.num_beams,
                                           max_new_tokens=args.max_new_tokens,
                                           length_penalty=args.length_penalty)
            else:
                out = generate(model, params, ids, mask,
                               max_new_tokens=args.max_new_tokens,
                               temperature=args.temperature,
                               top_k=args.top_k, top_p=args.top_p,
                               seed=args.seed)
        elif (getattr(args, "draft_dir", None)
                or getattr(args, "self_speculate_layers", 0)):
            # speculative decoding: token-exact greedy at temperature 0,
            # distribution-exact rejection sampling at temperature > 0;
            # knobs it can't honor are refused, not silently ignored
            spec_flag = ("--draft_dir" if args.draft_dir
                         else "--self_speculate_layers")
            if (args.top_k or args.top_p) and not args.temperature:
                raise SystemExit(
                    f"{spec_flag}: --top_k/--top_p need --temperature "
                    "> 0 (greedy speculation is argmax, which filtering "
                    "cannot change)")
            if args.num_beams > 1:
                raise SystemExit(f"{spec_flag} cannot combine with "
                                 "--num_beams (speculative decode is "
                                 "greedy)")
            if args.self_speculate_layers < 0:
                raise SystemExit("--self_speculate_layers must be >= 1")
            from huggingface_sagemaker_tensorflow_distributed_tpu.models.generate import (
                generate_speculative,
                self_draft,
            )

            if args.draft_dir and args.self_speculate_layers:
                raise SystemExit("--draft_dir and --self_speculate_layers "
                                 "are mutually exclusive")
            if args.self_speculate_layers:
                # layer-skip self-speculation: the draft is the target's
                # own first N layers — no second checkpoint
                draft_model, draft_params = self_draft(
                    model, params, args.self_speculate_layers)
            else:
                draft_model, draft_params, _, _ = auto_models.from_pretrained(
                    args.draft_dir, task="causal-lm")
            # bucket prompt widths to multiples of 32 (right-padded
            # masks), batch each bucket in ONE call: rows advance
            # independently inside the batched while_loop, and each
            # bucket width compiles once
            ids_np, mask_np = np.asarray(ids), np.asarray(mask)
            widths = [min(ids_np.shape[1],
                          ((int(mask_np[r].sum()) + 31) // 32) * 32)
                      for r in range(ids_np.shape[0])]
            rows = [None] * ids_np.shape[0]
            for w in sorted(set(widths)):
                sel = [r for r, rw in enumerate(widths) if rw == w]
                outs = np.asarray(generate_speculative(
                    model, params, draft_model, draft_params,
                    ids_np[sel][:, :w], mask_np[sel][:, :w],
                    max_new_tokens=args.max_new_tokens,
                    speculate_k=args.speculate_k,
                    temperature=args.temperature, top_k=args.top_k,
                    top_p=args.top_p, seed=args.seed))
                for i, r in enumerate(sel):
                    rows[r] = outs[i]
            out = np.stack(rows, axis=0)
        elif args.num_beams > 1:
            from huggingface_sagemaker_tensorflow_distributed_tpu.models.generate import (
                beam_search_causal,
            )

            if args.temperature or args.top_k or args.top_p:
                raise SystemExit("--num_beams is deterministic beam "
                                 "search; it cannot combine with "
                                 "--temperature/--top_k/--top_p")
            out = beam_search_causal(model, params, ids, mask,
                                     num_beams=args.num_beams,
                                     max_new_tokens=args.max_new_tokens,
                                     length_penalty=args.length_penalty)
        else:
            out = generate_causal(model, params, ids, mask,
                                  max_new_tokens=args.max_new_tokens,
                                  temperature=args.temperature,
                                  top_k=args.top_k, top_p=args.top_p,
                                  seed=args.seed,
                                  prefill_chunk=getattr(args,
                                                        "prefill_chunk", 0))
        for text, row in zip(texts, np.asarray(out)):
            results.append({"text": text,
                            "generated": tokenizer.decode(row),
                            "generated_ids": row.tolist()})
        return results

    # token_type_ids matter for pair inputs (QA): the trainer forwards
    # them (train/trainer.py::_apply), so inference must too
    # graftlint: allow[R3] no static key: params/ids/mask/type-ids are all traced arrays, the model is closed over — one compile per predict invocation by construction
    apply = jax.jit(lambda p, i, m, t: model.apply(
        {"params": p}, i, m, token_type_ids=t, deterministic=True))
    out = apply(params, ids, mask, token_types)

    if args.task == "seq-cls":
        probs = np.asarray(jax.nn.softmax(out.astype(jnp.float32), -1))
        for text, p in zip(texts, probs):
            results.append({"text": text, "label": int(p.argmax()),
                            "probs": [round(float(x), 4) for x in p]})
    elif args.task == "token-cls":
        pred = np.asarray(jnp.argmax(out, -1))
        am = np.asarray(mask)
        for r, text in enumerate(texts):
            toks = tokenizer.convert_ids_to_tokens(np.asarray(ids[r])[am[r] > 0])
            results.append({"text": text,
                            "tokens": toks,
                            "labels": pred[r][am[r] > 0].tolist()})
    elif args.task == "qa":
        start, end = out
        if qa_offsets is not None:
            # the eval metric's decode (utils/metrics.py): joint argmax
            # over context-token pairs, sliced from the original context;
            # start/end report the SAME winning span, so a result row is
            # internally consistent
            from huggingface_sagemaker_tensorflow_distributed_tpu.utils.metrics import (
                extract_answer_spans,
            )
            ex_ids = (qa_example_ids if qa_example_ids is not None
                      else np.arange(len(texts)))
            feat_ctx = [contexts[int(ex)] for ex in ex_ids]
            spans = extract_answer_spans(start, end, qa_offsets[0],
                                         qa_offsets[1], feat_ctx,
                                         with_spans=True, with_scores=True)
            # doc-stride: keep each input's highest-scoring window (token
            # indices are relative to THAT window's feature row)
            best = {}
            for (answer, s_tok, e_tok, score), ex in zip(spans, ex_ids):
                ex = int(ex)
                if ex not in best or score > best[ex][3]:
                    best[ex] = (answer, s_tok, e_tok, score)
            for r, text in enumerate(texts):
                answer, s_tok, e_tok, _ = best[r]
                results.append({"text": text, "start": s_tok,
                                "end": e_tok, "answer": answer})
        else:
            s = np.asarray(jnp.argmax(start, -1))
            e = np.asarray(jnp.argmax(end, -1))
            for r, text in enumerate(texts):
                lo, hi = int(s[r]), int(e[r])
                span_ids = np.asarray(ids[r])[lo: hi + 1] if hi >= lo else []
                results.append({"text": text, "start": lo, "end": hi,
                                "answer": tokenizer.decode(span_ids)})
    elif args.task == "rtd":
        # per-token probability that the token was replaced (ELECTRA
        # discriminator; sigmoid of the binary logit)
        probs = np.asarray(jax.nn.sigmoid(out.astype(jnp.float32)))
        am = np.asarray(mask)
        for r, text in enumerate(texts):
            toks = tokenizer.convert_ids_to_tokens(np.asarray(ids[r])[am[r] > 0])
            results.append({"text": text, "tokens": toks,
                            "replaced_prob": [round(float(x), 4)
                                              for x in probs[r][am[r] > 0]]})
    elif args.task == "mlm":
        mask_id = getattr(tokenizer, "mask_token_id", None)
        if mask_id is None:
            # without this, the elementwise ids == None comparison below
            # is all-False and every row silently gets empty 'fills'
            raise ValueError(
                "mlm prediction needs a tokenizer with a mask token "
                "(tokenizer.mask_token_id is None); same loud-failure "
                "convention as ArrayDataset.from_mlm_texts")
        if not np.any(np.asarray(ids) == mask_id):
            # in-repo tokenizers split a literal "[MASK]" into
            # punctuation; re-encode segment-wise around the marker
            enc = _encode_mlm_with_mask(tokenizer, texts, max_len, mask_id)
            ids = jnp.asarray(enc["input_ids"])
            mask = jnp.asarray(enc["attention_mask"])
            out = apply(params, ids, mask, None)
        logits = np.asarray(out)
        for r, text in enumerate(texts):
            row_ids = np.asarray(ids[r])
            fills = []
            for pos in np.flatnonzero(row_ids == mask_id):
                top = np.argsort(-logits[r, pos])[: args.top_k or 5]
                fills.append({"position": int(pos),
                              "top_tokens": tokenizer.convert_ids_to_tokens(top),
                              "top_ids": top.tolist()})
            results.append({"text": text, "fills": fills})
    else:
        raise ValueError(f"unknown task {args.task!r}")
    return results


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--model_dir", required=True)
    ap.add_argument("--task", default="seq-cls",
                    choices=["seq-cls", "token-cls", "qa", "seq2seq",
                             "causal-lm", "mlm", "rtd"])
    ap.add_argument("--text", default=None)
    ap.add_argument("--context", default=None)
    ap.add_argument("--input_file", default=None,
                    help="jsonl with {'text': ..., 'context'?: ...}")
    ap.add_argument("--num_labels", type=int, default=2)
    ap.add_argument("--adapter", default=None,
                    help="LoRA adapter dir (adapter.safetensors + "
                         "adapter_config.json) merged onto the base "
                         "checkpoint at load")
    ap.add_argument("--doc_stride", type=int, default=0,
                    help="QA: window long contexts with this token stride "
                         "instead of truncating (HF run_qa; 0 = off)")
    ap.add_argument("--prefill_chunk", type=int, default=0,
                    help="split long-prompt prefill into fixed-size "
                         "chunks (causal-lm; O(chunk) attention memory "
                         "instead of O(prompt), same tokens out)")
    ap.add_argument("--kv_cache", choices=["fp", "int8"], default="fp",
                    help="decode KV cache storage (Llama family + "
                         "GPT-2): int8 halves cache bytes read per "
                         "step at long context")
    ap.add_argument("--draft_dir", default=None,
                    help="draft-model checkpoint dir for speculative "
                         "decoding (causal-lm, or seq2seq for the T5 "
                         "family; greedy-exact at temperature 0: the "
                         "draft changes speed, never tokens)")
    ap.add_argument("--speculate_k", type=int, default=4,
                    help="draft tokens per verify window (--draft_dir / "
                         "--self_speculate_layers)")
    ap.add_argument("--self_speculate_layers", type=int, default=0,
                    help="layer-skip self-speculation: draft = the "
                         "target's own first N layers (no draft "
                         "checkpoint; greedy-exact like --draft_dir)")
    ap.add_argument("--quantize", choices=["none", "int8"], default="none",
                    help="int8 weight-only dense kernels for causal-lm "
                         "generation (HBM-bound decode speedup)")
    ap.add_argument("--max_seq_length", type=int, default=512)
    ap.add_argument("--max_new_tokens", type=int, default=64)
    ap.add_argument("--num_beams", type=int, default=1)
    ap.add_argument("--length_penalty", type=float, default=1.0)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top_k", type=int, default=0)
    ap.add_argument("--top_p", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    return ap


def main(argv=None):
    ap = build_parser()
    args = ap.parse_args(argv)
    if not args.text and not args.input_file:
        ap.error("provide --text or --input_file")
    require_accelerator()
    enable_compilation_cache()
    for row in predict(args):
        print(json.dumps(row))


if __name__ == "__main__":
    main(sys.argv[1:])
