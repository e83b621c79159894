"""Input pipeline: static-shape batching, per-host sharding, device feed.

TPU-native replacement for the reference's tf.data layer (reference
``scripts/train.py:78-100``: ``set_format("tensorflow")`` → densify to
``[N, 512]`` → ``from_tensor_slices(...).batch(...)``), with the two
fixes SURVEY.md §2 calls out:

- **Per-host sharding**: the reference feeds every worker the FULL
  dataset (K workers ⇒ K× data per "epoch"). Here every host sees the
  same epoch-seeded global permutation and takes only its slice of each
  global batch; the global batch = per-chip batch × DP size, the
  semantics the reference documents at ``scripts/train.py:143-144``.
- **Static shapes under XLA**: train batches drop the remainder; eval
  batches pad the tail and carry a ``valid`` mask so padded rows are
  excluded from metrics (tf.data could hand Keras a ragged final batch,
  ``scripts/train.py:98-100``; TPU cannot).

Device feed builds one global ``jax.Array`` per batch from
process-local shards (``jax.make_array_from_process_local_data``) —
single-host and multi-host use the identical code path. Host→device
transfer overlaps compute via a one-batch lookahead (JAX dispatch is
async), replacing tf.data's prefetch.
"""

from __future__ import annotations

import collections
import queue
import threading
import time
from dataclasses import dataclass
from typing import Iterator, Optional, Union

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding

from huggingface_sagemaker_tensorflow_distributed_tpu import obs
from huggingface_sagemaker_tensorflow_distributed_tpu.data.autotune import (
    PrefetchAutotuner,
)
from huggingface_sagemaker_tensorflow_distributed_tpu.parallel.sharding import (
    batch_column_sharding,
)


def encode_mlm_clean(tokenizer, texts, max_length: int):
    """Tokenize an MLM corpus WITHOUT masking: (clean_ids, attention_mask,
    word_ids), the inputs every masking draw starts from. Shared by the
    materialized (``MlmDataset``) and streaming tiers."""
    import re as _re

    if getattr(tokenizer, "mask_token_id", None) is None:
        raise ValueError(
            "tokenizer has no [MASK] token — MLM needs one "
            "(BERT-family vocabs ship it)")
    if hasattr(tokenizer, "encode_text_words"):
        # HF fast tokenizers: native tokenization of the raw text
        # (byte-BPE spacing preserved) + word_ids from the encoding
        enc = tokenizer.encode_text_words(texts, max_length=max_length)
    else:
        words = [_re.findall(r"\w+|[^\w\s]", t) for t in texts]
        enc = tokenizer.encode_words(words, max_length=max_length)
    return (np.asarray(enc["input_ids"], np.int32),
            np.asarray(enc["attention_mask"], np.int32),
            np.asarray(enc["word_ids"], np.int32))


@dataclass
class ArrayDataset:
    """Column dict of host-resident numpy arrays with equal leading dim."""

    columns: dict[str, np.ndarray]

    def __post_init__(self):
        sizes = {k: len(v) for k, v in self.columns.items()}
        if len(set(sizes.values())) > 1:
            raise ValueError(f"ragged columns: {sizes}")

    def __len__(self) -> int:
        return len(next(iter(self.columns.values())))

    def __getitem__(self, idx) -> dict[str, np.ndarray]:
        if isinstance(idx, np.ndarray) and idx.ndim == 1:
            # batch gather through the native loader (parallel memcpy,
            # native/dataloader.cc) — falls back to numpy fancy indexing
            from huggingface_sagemaker_tensorflow_distributed_tpu.data.native import (
                native_gather,
            )
            return {k: native_gather(v, idx) for k, v in self.columns.items()}
        return {k: v[idx] for k, v in self.columns.items()}

    def pack(self, max_length: Optional[int] = None,
             causal: bool = False, pad_token_id: int = 0) -> "ArrayDataset":
        """Token-packed view of this dataset (see :func:`pack_examples`):
        short examples share rows, with ``segment_ids``/``position_ids``
        columns keeping attention and positions per-example — the pad
        waste that length bucketing alone leaves on the table goes to
        ~zero. Token-level tasks only (causal-lm with ``causal=True``,
        mlm/token-cls with the default); per-example labels cannot pack.
        """
        if getattr(self, "begin_epoch", None) is not None:
            raise ValueError(
                "packing re-groups rows at build time, which would freeze "
                "this dataset's per-epoch transform (MLM re-masking) — "
                "pack a plain ArrayDataset (e.g. static_masking=True)")
        if max_length is None:
            max_length = self.columns["attention_mask"].shape[1]
        return ArrayDataset(pack_examples(self.columns, max_length,
                                          causal=causal,
                                          pad_token_id=pad_token_id))

    @classmethod
    def from_texts(cls, tokenizer, texts, labels=None, max_length: int = 512,
                   text_pairs=None) -> "ArrayDataset":
        """Tokenize-and-densify, the reference's map+to_tensor step
        (``scripts/train.py:75-83``) in one call with static shapes."""
        enc = tokenizer(texts, truncation=True, padding="max_length",
                        max_length=max_length, text_pairs=text_pairs)
        cols = {"input_ids": enc["input_ids"], "attention_mask": enc["attention_mask"]}
        if "token_type_ids" in enc:
            cols["token_type_ids"] = enc["token_type_ids"]
        if labels is not None:
            cols["labels"] = np.asarray(labels, np.int32)
        return cls(cols)

    @classmethod
    def from_mlm_texts(cls, tokenizer, texts, max_length: int = 512,
                       mlm_probability: float = 0.15, whole_word: bool = True,
                       seed: int = 0,
                       static_masking: bool = False) -> "MlmDataset":
        """Masked-LM corpus with (whole-word) masking — the pretraining
        recipe behind the reference's default checkpoint
        ``bert-large-uncased-whole-word-masking`` (reference
        ``launch.py:17``). HF ``DataCollatorForWholeWordMask`` semantics:
        ``mlm_probability`` of WORDS are chosen (every subword of a
        chosen word is predicted); chosen tokens become [MASK] 80% /
        random 10% / unchanged 10%; labels are -100 elsewhere.

        Returns an :class:`MlmDataset`: masks are RE-DRAWN each epoch
        (``ShardedBatcher`` calls ``begin_epoch``), matching HF's
        per-batch collator diversity; eval paths iterate with
        ``epoch=0`` so held-out masks stay fixed."""
        ids, am, wid = encode_mlm_clean(tokenizer, texts, max_length)
        return MlmDataset(
            clean_ids=ids, attention_mask=am, word_ids=wid,
            mask_token_id=int(tokenizer.mask_token_id),
            vocab_size=int(getattr(tokenizer, "vocab_size")),
            mlm_probability=mlm_probability, whole_word=whole_word,
            seed=seed, static_masking=static_masking)

    @classmethod
    def from_span_corruption_texts(cls, tokenizer, texts,
                                   max_source_length: int = 512,
                                   max_target_length: int = 114,
                                   corruption_rate: float = 0.15,
                                   mean_span_length: float = 3.0,
                                   n_sentinels: int = 100,
                                   decoder_start_token_id: int = 0,
                                   pad_token_id: int = 0,
                                   eos_token_id: int = 1,
                                   seed: int = 0) -> "ArrayDataset":
        """T5 span-corruption pretraining (the objective behind every T5
        checkpoint): ~``corruption_rate`` of tokens are dropped in spans
        of mean length ``mean_span_length``; each span is replaced by a
        sentinel (<extra_id_i> = vocab_size-1-i, descending) in the
        source, and the target interleaves sentinels with the dropped
        spans plus a final sentinel — the paper's layout::

            source: Thank you <X> me to your party <Y> week .
            target: <X> for inviting <Y> last <Z>
        """
        from huggingface_sagemaker_tensorflow_distributed_tpu.models.t5 import (
            shift_right,
        )

        enc = tokenizer(texts, truncation=True, padding="max_length",
                        max_length=max_source_length,
                        add_special_tokens=False)
        ids = np.asarray(enc["input_ids"], np.int32)
        am = np.asarray(enc["attention_mask"], np.int32)
        vocab = int(getattr(tokenizer, "vocab_size"))
        rng = np.random.RandomState(seed)

        def partition(total: int, parts: int) -> list[int]:
            """total split into ``parts`` random segments, each >= 1."""
            cuts = np.sort(rng.choice(total - 1, parts - 1, replace=False)) + 1 \
                if parts > 1 else np.array([], np.int64)
            bounds = np.concatenate([[0], cuts, [total]])
            return list(np.diff(bounds))

        n_rows = ids.shape[0]
        src = np.full((n_rows, max_source_length), pad_token_id, np.int32)
        src_mask = np.zeros((n_rows, max_source_length), np.int32)
        tgt_ids = np.full((n_rows, max_target_length), pad_token_id, np.int32)
        tgt_mask = np.zeros((n_rows, max_target_length), np.int32)
        for r in range(n_rows):
            toks = ids[r][am[r] > 0]
            n = len(toks)
            if n < 4:
                src[r, :n] = toks
                src[r, min(n, max_source_length - 1)] = eos_token_id
                src_mask[r, : min(n + 1, max_source_length)] = 1
                tgt_ids[r, 0] = eos_token_id
                tgt_mask[r, 0] = 1
                continue
            num_noise = int(np.clip(round(n * corruption_rate), 1, n - 2))
            # num_spans+1 keep-segments of >= 1 token must fit in the
            # n - num_noise kept tokens
            num_spans = int(np.clip(round(num_noise / mean_span_length),
                                    1, min(num_noise, n - num_noise - 1,
                                           n_sentinels - 1)))
            noise_lens = partition(num_noise, num_spans)
            keep_lens = partition(n - num_noise, num_spans + 1)
            s_row: list[int] = []
            t_row: list[int] = []
            pos = 0
            for i in range(num_spans):
                sentinel = vocab - 1 - i
                s_row += toks[pos: pos + keep_lens[i]].tolist() + [sentinel]
                pos += keep_lens[i]
                t_row += [sentinel] + toks[pos: pos + noise_lens[i]].tolist()
                pos += noise_lens[i]
            s_row += toks[pos:].tolist() + [eos_token_id]  # T5 inputs end </s>
            t_row += [vocab - 1 - num_spans]          # final sentinel
            s_row = s_row[:max_source_length]
            t_row = t_row[: max_target_length - 1] + [eos_token_id]
            src[r, : len(s_row)] = s_row
            src_mask[r, : len(s_row)] = 1
            tgt_ids[r, : len(t_row)] = t_row
            tgt_mask[r, : len(t_row)] = 1
        labels = np.where(tgt_mask > 0, tgt_ids, -100).astype(np.int32)
        dec_in = np.asarray(shift_right(labels, decoder_start_token_id,
                                        pad_token_id), np.int32)
        return cls({"input_ids": src, "attention_mask": src_mask,
                    "decoder_input_ids": dec_in,
                    "decoder_attention_mask": tgt_mask,
                    "labels": labels})

    @classmethod
    def from_rtd_texts(cls, tokenizer, texts, max_length: int = 512,
                       replace_probability: float = 0.15,
                       seed: int = 0) -> "ArrayDataset":
        """Replaced-token-detection corpus (ELECTRA pretraining shape):
        ~``replace_probability`` of real tokens are swapped for random
        vocab ids; labels are 1 where the id actually changed, 0 on
        untouched tokens, -100 on specials/pads. (Real ELECTRA samples
        replacements from a trained generator; random replacement is the
        standard offline/ablation tier.)"""
        enc = tokenizer(texts, truncation=True, padding="max_length",
                        max_length=max_length)
        ids = np.asarray(enc["input_ids"], np.int32).copy()
        am = np.asarray(enc["attention_mask"], np.int32)
        specials = {getattr(tokenizer, name, None)
                    for name in ("pad_token_id", "cls_token_id",
                                 "sep_token_id", "mask_token_id")}
        real = (am > 0) & ~np.isin(ids, [s for s in specials if s is not None])
        rng = np.random.RandomState(seed)
        vocab = int(getattr(tokenizer, "vocab_size"))
        pick = real & (rng.rand(*ids.shape) < replace_probability)
        draws = rng.randint(0, vocab, ids.shape).astype(np.int32)
        changed = pick & (draws != ids)
        labels = np.where(real, 0, -100).astype(np.int32)
        labels[changed] = 1
        ids = np.where(changed, draws, ids)
        return cls({"input_ids": ids, "attention_mask": am, "labels": labels})

    @classmethod
    def from_lm_texts(cls, tokenizer, texts, max_length: int = 512,
                      packed: bool = False,
                      eos_token_id: Optional[int] = None) -> "ArrayDataset":
        """Causal-LM corpus: labels are the input ids themselves (the
        trainer's causal-lm loss shifts them); pad positions get -100.

        ``packed=True`` is the TPU pretraining layout: documents are
        tokenized without padding, joined by EOS, and chunked into
        completely-full ``max_length`` rows — zero pad waste, so every
        MXU cycle trains on real tokens (GPT-2-style packing; documents
        attend across boundaries, the standard trade). The tail chunk
        that would need padding is dropped."""
        if not packed:
            enc = tokenizer(texts, truncation=True, padding="max_length",
                            max_length=max_length)
            ids = np.asarray(enc["input_ids"], np.int32)
            mask = np.asarray(enc["attention_mask"], np.int32)
            labels = np.where(mask > 0, ids, -100).astype(np.int32)
            return cls({"input_ids": ids, "attention_mask": mask,
                        "labels": labels})
        if eos_token_id is None:
            eos_token_id = getattr(tokenizer, "eos_token_id", None)
        if eos_token_id is None:
            eos_token_id = getattr(tokenizer, "sep_token_id", None)
        if eos_token_id is None:
            raise ValueError(
                "packed=True joins documents with EOS, but the tokenizer "
                "has neither eos_token_id nor sep_token_id — pass "
                "eos_token_id explicitly")
        vocab = getattr(tokenizer, "vocab_size", None)
        try:
            # HF vocab_size excludes ADDED tokens (a post-training eos is
            # legal); len(tokenizer) is the total when exposed
            vocab = max(int(vocab), len(tokenizer))
        except TypeError:
            pass
        if vocab is not None and not 0 <= int(eos_token_id) < int(vocab):
            raise ValueError(
                f"packed=True separator id {eos_token_id} is outside the "
                f"tokenizer vocab ({vocab}): the model would embed an "
                "out-of-range id every document boundary (a config.json "
                "with the default GPT-2 eos 50256 on a small-vocab test "
                "model is the usual culprit) — pass a valid eos_token_id")
        # chunked batched tokenization (longest + no truncation): each
        # chunk pads only to its own longest row, so peak memory stays
        # O(total tokens) even with one outlier-length document
        stream: list[int] = []
        texts = list(texts)
        for lo in range(0, len(texts), 1024):
            enc = tokenizer(texts[lo: lo + 1024], truncation=False,
                            padding="longest", max_length=1 << 20,
                            add_special_tokens=False)
            all_ids = np.asarray(enc["input_ids"])
            all_mask = np.asarray(enc["attention_mask"]) > 0
            for r in range(all_ids.shape[0]):
                stream.extend(all_ids[r][all_mask[r]].tolist())
                stream.append(int(eos_token_id))
        n_rows = len(stream) // max_length
        if n_rows == 0:
            raise ValueError(
                f"packed corpus shorter than one {max_length}-token row")
        ids = np.asarray(stream[: n_rows * max_length],
                         np.int32).reshape(n_rows, max_length)
        mask = np.ones_like(ids)
        return cls({"input_ids": ids, "attention_mask": mask,
                    "labels": ids.copy()})

    @classmethod
    def from_token_classification(cls, tokenizer, sentences, word_tags,
                                  max_length: int = 512) -> "ArrayDataset":
        """Word-level NER → token-level labels, -100 on specials/pads and
        on continuation subwords (label only the first subword of each
        word — the HF convention the token-cls loss masks on)."""
        enc = tokenizer.encode_words(sentences, max_length=max_length)
        word_ids = enc["word_ids"]
        n, L = word_ids.shape
        labels = np.full((n, L), -100, np.int32)
        for r in range(n):
            tags = word_tags[r]
            prev = -1
            for t in range(L):
                w = word_ids[r, t]
                if w < 0 or w >= len(tags):
                    continue
                if w != prev:
                    labels[r, t] = tags[w]
                prev = w
        return cls({"input_ids": enc["input_ids"],
                    "attention_mask": enc["attention_mask"],
                    "labels": labels})

    @classmethod
    def from_qa(cls, tokenizer, questions, contexts, start_chars, answer_texts,
                max_length: int = 512, doc_stride: int = 0) -> "ArrayDataset":
        """SQuAD-style spans → start/end token positions. ``doc_stride``
        > 0 trains on overlapping context windows (HF run_qa) instead of
        truncating long contexts — each window is an independent row,
        labeled iff it contains the full answer."""
        enc = dict(tokenizer.encode_qa(questions, contexts, start_chars,
                                       answer_texts, max_length=max_length,
                                       doc_stride=doc_stride))
        # feature→example map is an eval-side concern; training rows are
        # independent and the loss must not see the extra column
        enc.pop("example_ids", None)
        return cls(enc)

    @classmethod
    def from_seq2seq(cls, tokenizer, sources, targets,
                     max_source_length: int = 512,
                     max_target_length: int = 64,
                     decoder_start_token_id: int = 0,
                     pad_token_id: int = 0,
                     eos_token_id: int = 1) -> "ArrayDataset":
        """Source/target text pairs → encoder inputs + teacher-forcing
        decoder inputs + ``-100``-masked LM labels (T5 shift-right
        convention; the seq2seq breadth config of BASELINE.json).

        Targets are encoded LM-style — raw tokens + the MODEL's EOS, no
        CLS/SEP wrapping — so generation's stop condition matches what the
        decoder was trained to emit regardless of tokenizer flavor.
        """
        from huggingface_sagemaker_tensorflow_distributed_tpu.models.t5 import (
            shift_right,
        )
        enc = tokenizer(sources, truncation=True, padding="max_length",
                        max_length=max_source_length)
        tgt = tokenizer(targets, truncation=True, padding="max_length",
                        max_length=max_target_length - 1,
                        add_special_tokens=False)
        raw_ids = tgt["input_ids"].astype(np.int32)
        raw_mask = tgt["attention_mask"].astype(np.int32)
        n = raw_ids.shape[0]
        tgt_ids = np.full((n, max_target_length), pad_token_id, np.int32)
        tgt_mask = np.zeros((n, max_target_length), np.int32)
        tgt_ids[:, :-1] = np.where(raw_mask > 0, raw_ids, pad_token_id)
        tgt_mask[:, :-1] = raw_mask
        lengths = raw_mask.sum(axis=1)
        tgt_ids[np.arange(n), lengths] = eos_token_id
        tgt_mask[np.arange(n), lengths] = 1
        labels = np.where(tgt_mask > 0, tgt_ids, -100).astype(np.int32)
        dec_in = np.asarray(shift_right(labels, decoder_start_token_id,
                                        pad_token_id), np.int32)
        return cls({"input_ids": enc["input_ids"],
                    "attention_mask": enc["attention_mask"],
                    "decoder_input_ids": dec_in,
                    "decoder_attention_mask": tgt_mask,
                    "labels": labels})


def pack_examples(columns: dict[str, np.ndarray], max_length: int,
                  causal: bool = False,
                  pad_token_id: int = 0) -> dict[str, np.ndarray]:
    """Token-pack a column dict: multiple short examples per row, with
    ``segment_ids`` (1-based per-example id, 0 on padding) and
    ``position_ids`` (restarting at 0 per example) columns so attention
    stays cross-contamination-safe (``ops.attention.make_segment_mask``,
    the Krell et al. 2021 construction) and positional embeddings match
    the unpacked encode exactly.

    Examples are placed first-fit-decreasing into ``max_length`` rows —
    deterministic, so every host packs identically. All 2-D columns are
    packed by copying each example's first ``len`` positions (its real
    tokens per ``attention_mask``); padding gets mask 0, segment 0 and
    label -100. Per-example scalar columns (seq-cls labels) cannot pack
    and raise.

    ``causal=True`` additionally sets each segment's FIRST token label
    to -100: causal-LM losses shift labels left, so the target aligned
    with a segment boundary would be the next example's first token — a
    cross-contamination leak the mask cannot catch. Unpacked training
    never uses that label (the shift drops row position 0), so masking
    it keeps packed loss sums exactly equal to unpacked ones.
    """
    if "input_ids" not in columns or "attention_mask" not in columns:
        raise ValueError("packing needs input_ids + attention_mask columns")
    n, width = columns["attention_mask"].shape
    bad = [k for k, v in columns.items() if v.ndim != 2 or v.shape[1] != width]
    if bad:
        raise ValueError(
            f"columns {bad} are not [N, {width}] token columns — packing "
            "merges examples along the token dim, so per-example scalars "
            "(seq-cls labels) and ragged widths cannot pack")
    lengths = (columns["attention_mask"] > 0).sum(axis=1).astype(np.int64)
    if int(lengths.max(initial=0)) > max_length:
        raise ValueError(
            f"example of length {int(lengths.max())} exceeds the packed "
            f"row width {max_length}")
    # first-fit decreasing, stable on ties: identical on every host
    order = np.argsort(-lengths, kind="stable")
    bins: list[list[int]] = []
    space: list[int] = []
    for e in order:
        need = int(lengths[e])
        if need == 0:
            continue  # fully-empty rows carry no tokens: drop
        for b, free in enumerate(space):
            if free >= need:
                bins[b].append(int(e))
                space[b] -= need
                break
        else:
            bins.append([int(e)])
            space.append(max_length - need)
    rows = len(bins)
    out: dict[str, np.ndarray] = {}
    for k, v in columns.items():
        fill = -100 if k == "labels" else (
            pad_token_id if k == "input_ids" else 0)
        out[k] = np.full((rows, max_length), fill, v.dtype)
    out["segment_ids"] = np.zeros((rows, max_length), np.int32)
    out["position_ids"] = np.zeros((rows, max_length), np.int32)
    for r, members in enumerate(bins):
        o = 0
        for s, e in enumerate(members):
            ln = int(lengths[e])
            sel = columns["attention_mask"][e] > 0
            for k, v in columns.items():
                out[k][r, o: o + ln] = v[e][sel]
            out["segment_ids"][r, o: o + ln] = s + 1
            out["position_ids"][r, o: o + ln] = np.arange(ln)
            if causal and "labels" in out:
                out["labels"][r, o] = -100
            o += ln
    return out


def apply_mlm_masking(clean_ids: np.ndarray, word_ids: np.ndarray,
                      rng: "np.random.RandomState", mask_token_id: int,
                      vocab_size: int, mlm_probability: float = 0.15,
                      whole_word: bool = True) -> tuple[np.ndarray, np.ndarray]:
    """One vectorized masking draw over ``[n, L]`` clean token rows →
    ``(input_ids, labels)``. HF collator semantics: ``mlm_probability``
    of words chosen (≥1 per row with words), chosen tokens become [MASK]
    80% / random 10% / unchanged 10%, labels -100 elsewhere. Draw count
    depends only on the shapes, so a fixed-seed ``rng`` is reproducible."""
    ids = clean_ids.copy()
    labels = np.full_like(ids, -100)
    wid = word_ids
    n, width = ids.shape
    n_words = np.maximum(wid.max(axis=1) + 1, 0)
    has_words = n_words > 0
    if whole_word:
        max_w = max(int(n_words.max()), 1)
        chosen = rng.rand(n, max_w) < mlm_probability
        # positions past a row's word count never matter (wid never
        # points there), but "at least one word chosen" must only
        # consider real words
        real_w = np.arange(max_w)[None, :] < n_words[:, None]
        none = has_words & ~(chosen & real_w).any(axis=1)
        idx = np.flatnonzero(none)
        if len(idx):
            pick = (rng.rand(len(idx)) * n_words[idx]).astype(np.int64)
            chosen[idx, pick] = True
        sel = (wid >= 0) & np.take_along_axis(
            chosen, np.maximum(wid, 0), axis=1)
    else:
        sel = (wid >= 0) & (rng.rand(n, width) < mlm_probability)
        none = has_words & ~sel.any(axis=1)
        for r in np.flatnonzero(none):
            cand = np.flatnonzero(wid[r] >= 0)
            sel[r, cand[rng.randint(len(cand))]] = True
    labels[sel] = clean_ids[sel]
    action = rng.rand(n, width)
    ids[sel & (action < 0.8)] = mask_token_id
    do_rand = sel & (action >= 0.8) & (action < 0.9)
    ids[do_rand] = rng.randint(0, vocab_size,
                               int(do_rand.sum())).astype(ids.dtype)
    return ids, labels


class MlmDataset(ArrayDataset):
    """ArrayDataset whose MLM masking is re-drawn per epoch.

    Holds the CLEAN token ids + word ids; ``begin_epoch(e)`` materializes
    ``input_ids``/``labels`` from ``RandomState(seed + e)`` — fully
    vectorized, so a redraw costs one pass over the corpus, and every
    host derives identical masks with no communication (same seed
    discipline as ``ShardedBatcher``'s epoch permutation). Fixes the
    static-masking quirk where every epoch saw identical masks (HF's
    ``DataCollatorForWholeWordMask`` redraws per batch; per-epoch is the
    same diversity at epoch granularity)."""

    def __init__(self, clean_ids: np.ndarray, attention_mask: np.ndarray,
                 word_ids: np.ndarray, mask_token_id: int, vocab_size: int,
                 mlm_probability: float = 0.15, whole_word: bool = True,
                 seed: int = 0, static_masking: bool = False):
        self._clean_ids = clean_ids
        self._word_ids = word_ids
        self._mask_token_id = mask_token_id
        self._vocab_size = vocab_size
        self._mlm_probability = mlm_probability
        self._whole_word = whole_word
        self._seed = seed
        self._static = static_masking
        self._epoch: Optional[int] = None
        super().__init__({"attention_mask": attention_mask})
        self.begin_epoch(0)

    def pack(self, max_length: Optional[int] = None,
             causal: bool = False, pad_token_id: int = 0) -> "ArrayDataset":
        """Packing freezes row grouping at build time, which is only
        sound when the masking draw is pinned (``static_masking``): the
        seed draw's columns pack as a plain :class:`ArrayDataset`.
        Per-epoch re-masking cannot combine with packing — packed rows'
        word ids no longer align with the clean corpus."""
        if not self._static:
            raise ValueError(
                "packing an MLM dataset freezes the masking draw, so it "
                "requires static_masking=True (per-epoch re-masking "
                "cannot re-mask packed rows)")
        self.begin_epoch(0)
        return ArrayDataset(dict(self.columns)).pack(
            max_length, causal=causal, pad_token_id=pad_token_id)

    def begin_epoch(self, epoch: int) -> None:
        """Re-draw masks for ``epoch`` (idempotent per epoch).
        ``static_masking`` pins every epoch to the seed draw — the
        pre-r4 behavior, kept as an ablation knob."""
        if self._static:
            epoch = 0
        if epoch == self._epoch:
            return
        ids, labels = apply_mlm_masking(
            self._clean_ids, self._word_ids,
            np.random.RandomState(self._seed + epoch),
            self._mask_token_id, self._vocab_size,
            self._mlm_probability, self._whole_word)
        self.columns["input_ids"] = ids
        self.columns["labels"] = labels
        self._epoch = epoch


_PREFETCH_END = object()


class _AdaptiveQueue:
    """Bounded FIFO whose capacity can change while threads wait on it —
    what the prefetch autotuner adjusts. Mirrors the ``queue.Queue``
    subset the producer/consumer use (``put`` with timeout raising
    ``queue.Full``, blocking ``get``, ``get_nowait`` raising
    ``queue.Empty``); a capacity change wakes blocked producers so a
    deeper queue takes effect immediately."""

    def __init__(self, capacity: int):
        self._capacity = max(1, int(capacity))
        self._items: collections.deque = collections.deque()
        self._cond = threading.Condition()

    @property
    def capacity(self) -> int:
        return self._capacity

    def set_capacity(self, capacity: int) -> None:
        with self._cond:
            self._capacity = max(1, int(capacity))
            self._cond.notify_all()

    def qsize(self) -> int:
        return len(self._items)

    def put(self, item, timeout: Optional[float] = None) -> None:
        with self._cond:
            ok = self._cond.wait_for(
                lambda: len(self._items) < self._capacity, timeout=timeout)
            if not ok:
                raise queue.Full
            self._items.append(item)
            self._cond.notify_all()

    def get(self, timeout: Optional[float] = None):
        with self._cond:
            ok = self._cond.wait_for(lambda: len(self._items) > 0,
                                     timeout=timeout)
            if not ok:
                raise queue.Empty
            item = self._items.popleft()
            self._cond.notify_all()
            return item

    def get_nowait(self):
        return self.get(timeout=0)


class _PrefetchStats:
    """Producer-wait vs consumer-wait accounting: makes input-bound vs
    compute-bound a one-glance read in the telemetry stream.

    - ``producer_wait``: the producer thread sat on a FULL queue — the
      input pipeline is AHEAD of the device (compute-bound, good).
    - ``consumer_wait``: the train loop sat on an EMPTY queue — the
      device waited for data (input-bound: raise prefetch depth, speed
      up tokenization/gather).
    """

    __slots__ = ("producer_wait", "consumer_wait", "produced", "consumed",
                 "_reported")

    def __init__(self):
        self.producer_wait = 0.0
        self.consumer_wait = 0.0
        self.produced = 0
        self.consumed = 0
        self._reported = False

    def report(self, depth: Optional[int] = None) -> None:
        if self._reported or not self.consumed:
            return
        self._reported = True
        obs.scalar("data/producer_wait_s", self.producer_wait,
                   args={"batches": self.produced})
        consumer_args = {"batches": self.consumed,
                         "verdict": ("input_bound"
                                     if self.consumer_wait
                                     > self.producer_wait
                                     else "compute_bound")}
        if depth is not None:
            # achieved (final) prefetch depth, so the autotuner's end
            # state reads off the same line as the wait verdict
            consumer_args["depth"] = int(depth)
        obs.scalar("data/consumer_wait_s", self.consumer_wait,
                   args=consumer_args)


def _prefetch_producer(it, q: queue.Queue, stop: threading.Event,
                       stats: _PrefetchStats) -> None:
    # module-level target: the thread must NOT strongly reference the
    # PrefetchIterator, or threading's live-thread registry would keep it
    # reachable and the GC finalizer could never fire
    try:
        for item in it:
            t0 = time.perf_counter()
            while not stop.is_set():
                try:
                    q.put(item, timeout=0.1)
                    break
                except queue.Full:
                    continue
            stats.producer_wait += time.perf_counter() - t0
            stats.produced += 1
            if stop.is_set():
                return
        q.put(_PREFETCH_END)
    except BaseException as e:  # noqa: BLE001 — re-raised in consumer
        if not stop.is_set():
            q.put(e)


def _drain_and_stop(q: queue.Queue, stop: threading.Event) -> None:
    stop.set()
    # drain so a producer blocked on put() observes the stop flag
    try:
        while True:
            q.get_nowait()
    except queue.Empty:
        pass


def _batch_nbytes(item) -> int:
    """Host bytes one queued batch pins (dict of numpy columns; 0 when
    the item shape is unknown — the autotuner then skips the mem cap)."""
    if isinstance(item, dict):
        return sum(int(getattr(v, "nbytes", 0)) for v in item.values())
    return int(getattr(item, "nbytes", 0))


class PrefetchIterator:
    """Iterator wrapper that materializes up to ``depth`` items ahead on a
    daemon thread. Exceptions from the producer re-raise at the consumer;
    ``close()`` stops the producer promptly, and dropping the iterator
    without closing triggers the same cleanup via ``weakref.finalize`` so
    abandoned iterators don't pin prefetched device batches.

    With an ``autotuner`` (:class:`~.autotune.PrefetchAutotuner`) the
    depth is live: each consumed batch feeds the cumulative wait stats to
    the controller, and a decision resizes the queue in place (emitting
    an ``autotune`` telemetry event). Without one, ``depth`` is fixed —
    the pre-autotune behavior."""

    def __init__(self, it: Iterator, depth: int = 2,
                 autotuner: Optional[PrefetchAutotuner] = None):
        import weakref

        self._done = False
        self._autotuner = autotuner
        if autotuner is not None:
            depth = autotuner.depth
        self._queue = _AdaptiveQueue(depth)
        self._stop = threading.Event()
        self.stats = _PrefetchStats()
        self._thread = threading.Thread(
            target=_prefetch_producer,
            args=(it, self._queue, self._stop, self.stats),
            daemon=True)
        self._finalizer = weakref.finalize(
            self, _drain_and_stop, self._queue, self._stop)
        self._thread.start()

    @property
    def depth(self) -> int:
        return self._queue.capacity

    def __iter__(self):
        return self

    def __next__(self):
        if self._done:
            raise StopIteration
        with obs.span("data/next_batch"):
            t0 = time.perf_counter()
            item = self._queue.get()
            self.stats.consumer_wait += time.perf_counter() - t0
        if item is _PREFETCH_END:
            self._done = True
            self.stats.report(depth=self.depth)
            raise StopIteration
        if isinstance(item, BaseException):
            self._done = True
            raise item
        self.stats.consumed += 1
        if self._autotuner is not None:
            decision = self._autotuner.observe(
                self.stats.producer_wait, self.stats.consumer_wait,
                self.stats.consumed, _batch_nbytes(item))
            if decision is not None:
                new_depth, reason = decision
                self._queue.set_capacity(new_depth)
                obs.autotune("data/prefetch_depth", new_depth, reason,
                             args={"batches": self.stats.consumed})
        return item

    def close(self):
        if not self._done:
            self.stats.report(depth=self.depth)
        self._done = True
        self._finalizer()


_STAGER_END = object()


class H2DStager:
    """Device-side double buffer: overlap batch N+1's host→device
    transfer with compute on batch N.

    JAX dispatch is async, so the moment the consumer takes batch N and
    dispatches its step, this iterator starts batch N+1's transfer —
    one device batch is always in flight while the device computes,
    without queueing unbounded device memory (exactly two live batches:
    the one computing and the one staging; batch N's HBM frees for
    batch N+2's landing when the consumer's loop variable rebinds).

    Spans: each transfer dispatch is a ``data/h2d_stage`` span nested
    around the ``data/host_to_device`` put, so the overlap is visible in
    trace.json next to ``train/step_dispatch``; exhaustion emits one
    ``data/h2d_stage_s`` metric with total staging seconds + batches.
    """

    def __init__(self, host_iter, put_batch):
        self._it = host_iter
        self._put = put_batch
        self._pending = None
        self._primed = False
        self.stage_s = 0.0
        self.staged = 0
        self._reported = False

    def __iter__(self):
        return self

    def _stage(self):
        batch = next(self._it)  # StopIteration propagates to the caller
        t0 = time.perf_counter()
        with obs.span("data/h2d_stage"):
            out = self._put(batch)
        self.stage_s += time.perf_counter() - t0
        self.staged += 1
        return out

    def __next__(self):
        if self._pending is _STAGER_END:
            raise StopIteration
        if not self._primed:
            self._primed = True
            try:
                self._pending = self._stage()
            except StopIteration:
                self._pending = _STAGER_END
                self._report()
                raise
        current = self._pending
        try:
            self._pending = self._stage()
        except StopIteration:
            self._pending = _STAGER_END
            self._report()
        return current

    def _report(self) -> None:
        if self._reported or not self.staged:
            return
        self._reported = True
        obs.scalar("data/h2d_stage_s", self.stage_s,
                   args={"batches": self.staged})

    @property
    def stats(self) -> _PrefetchStats:
        """The wrapped host prefetcher's wait accounting (producer vs
        consumer wait — the autotuner's input), for callers that read
        ``it.stats`` off ``global_arrays`` iterators."""
        return self._it.stats

    @property
    def depth(self) -> int:
        return getattr(self._it, "depth", 0)

    def close(self):
        self._pending = _STAGER_END
        self._report()
        if hasattr(self._it, "close"):
            self._it.close()


class ShardedBatcher:
    """Iterates global batches, yielding this host's shard of each.

    All hosts construct the same epoch permutation (seeded by
    ``seed + epoch``), so the global batch order is agreed without any
    communication — the input-pipeline equivalent of the reference's
    rank-0 broadcast discipline.
    """

    def __init__(
        self,
        dataset: ArrayDataset,
        global_batch_size: int,
        mesh: Mesh,
        shuffle: bool = True,
        seed: int = 0,
        drop_remainder: bool = True,
        process_index: Optional[int] = None,
        process_count: Optional[int] = None,
        bucket_sizes: Optional[list[int]] = None,
        bucket_window: int = 16,
        pack: bool = False,
        pack_causal: bool = False,
    ):
        if pack:
            # token packing (pack_examples): short examples share rows
            # behind segment ids, so there is no pad waste left for the
            # bucket ladder to trim — the two modes are alternatives
            if bucket_sizes:
                raise ValueError(
                    "pack=True already eliminates pad waste; combining it "
                    "with bucket_sizes would re-fragment packed rows — "
                    "pick one")
            if not hasattr(dataset, "columns"):
                raise ValueError(
                    "pack=True re-groups rows at build time, which needs "
                    "a materialized dataset (streaming tiers tokenize "
                    "per batch)")
            dataset = dataset.pack(causal=pack_causal)
        self.dataset = dataset
        self.global_batch_size = global_batch_size
        self.mesh = mesh
        self.shuffle = shuffle
        self.seed = seed
        self.drop_remainder = drop_remainder
        self.bucket_sizes = sorted(bucket_sizes) if bucket_sizes else None
        self.bucket_window = bucket_window
        if self.bucket_sizes:
            # token columns shard over the ``seq`` mesh axis when present:
            # every bucket width must divide evenly or device_put fails
            # mid-epoch with an opaque sharding error
            from huggingface_sagemaker_tensorflow_distributed_tpu.parallel.mesh import (
                AXIS_SEQ,
            )
            sp = dict(mesh.shape).get(AXIS_SEQ, 1)
            bad = [b for b in self.bucket_sizes if b % sp != 0]
            if bad:
                raise ValueError(
                    f"bucket_sizes {bad} not divisible by the mesh seq axis "
                    f"(size {sp}); pad bucket widths to multiples of {sp}")
        self._lengths: dict[str, np.ndarray] = {}
        if self.bucket_sizes and not hasattr(dataset, "columns"):
            raise ValueError(
                "length bucketing needs corpus-wide token lengths, which "
                "streaming datasets deliberately don't precompute — drop "
                "bucket_sizes or materialize the dataset")
        if self.bucket_sizes:
            # token count per row, per mask column (native/dataloader.cc):
            # encoder and decoder widths bucket independently
            from huggingface_sagemaker_tensorflow_distributed_tpu.data.native import (
                native_row_lengths,
            )
            for name in ("attention_mask", "decoder_attention_mask"):
                if name in dataset.columns:
                    self._lengths[name] = native_row_lengths(dataset.columns[name])
        # bucket widths actually emitted (per mask column): when the XLA
        # compile budget is exceeded (HSTD_COMPILE_BUDGET_S, obs/), new
        # ladder rungs are capped to widths already compiled
        self._used_buckets: dict[str, set[int]] = {}
        # the last epoch's prefetch autotuner: its converged depth seeds
        # the next epoch's controller instead of re-learning from 2
        self._auto_tuner: Optional[PrefetchAutotuner] = None
        self.process_index = jax.process_index() if process_index is None else process_index
        self.process_count = jax.process_count() if process_count is None else process_count
        if global_batch_size % self.process_count != 0:
            raise ValueError(
                f"global batch {global_batch_size} not divisible by "
                f"{self.process_count} hosts")
        self.per_host = global_batch_size // self.process_count
        # column shardings depend only on (ndim, token dim): compute once,
        # not per column per step (mesh scans are host-side hot-path work)
        self._sharding_cache: dict[tuple, NamedSharding] = {}
        # MFU accounting (obs/flops.py): REAL token counts served by
        # THIS host — attention-mask nonzeros summed on the host numpy
        # batch just before device transfer, so the figure is
        # packing-aware by construction (pad positions never count).
        # ``token_log`` holds one (tokens, dec_tokens) entry PER BATCH
        # in yield order; the trainer pops one entry per dispatched
        # step, which keeps attribution exact under prefetch/H2D
        # lookahead (a staged-but-never-dispatched batch is cleared at
        # the next epoch). Bounded so non-popping consumers (eval) never
        # grow it. Counting is opt-in like every other obs cost: only
        # when something can consume an MFU figure — telemetry
        # configured, or a peak-FLOPs override set (the CPU bench path)
        # — does the H2D hot path pay the mask scan.
        from huggingface_sagemaker_tensorflow_distributed_tpu.obs.flops import (
            env_peak_tflops,
        )
        self._count_tokens = obs.enabled() and (
            obs.configured() or env_peak_tflops() is not None)
        self.token_log: collections.deque = collections.deque(maxlen=8192)

    def steps_per_epoch(self) -> int:
        n = len(self.dataset)
        if self.drop_remainder:
            return n // self.global_batch_size
        return (n + self.global_batch_size - 1) // self.global_batch_size

    def local_batches(self, epoch: int = 0, start_step: int = 0) -> Iterator[dict[str, np.ndarray]]:
        """Yield host-local numpy batches (with ``valid`` mask on eval tails).

        ``start_step`` skips already-consumed batches of this epoch's
        permutation — the data-position part of mid-epoch resume.
        """
        begin_epoch = getattr(self.dataset, "begin_epoch", None)
        if begin_epoch is not None:
            # per-epoch transforms (MLM re-masking): deterministic from
            # seed+epoch, so every host agrees and mid-epoch resume
            # (start_step) replays the identical columns
            begin_epoch(epoch)
        n = len(self.dataset)
        order = np.arange(n)
        if self.shuffle:
            # platform-independent epoch permutation (native/dataloader.cc;
            # Python twin gives the identical order without the toolchain) —
            # every host derives the same global order with no communication
            from huggingface_sagemaker_tensorflow_distributed_tpu.data.native import (
                native_permutation,
            )
            order = native_permutation(n, self.seed + epoch)
        if self.bucket_sizes:
            order = self._length_sorted_windows(order)
        steps = self.steps_per_epoch()
        for s in range(start_step, steps):
            if begin_epoch is not None:
                # re-assert before every gather: another batcher over the
                # SAME dataset object may have re-masked to its own epoch
                # between our yields (idempotent no-op in the sequential
                # train→eval pattern; NOT safe to interleave from two
                # threads concurrently)
                begin_epoch(epoch)
            lo = s * self.global_batch_size
            global_idx = order[lo: lo + self.global_batch_size]
            valid_n = len(global_idx)
            if valid_n < self.global_batch_size:
                pad = np.zeros(self.global_batch_size - valid_n, dtype=order.dtype)
                global_idx = np.concatenate([global_idx, pad])
            local_idx = global_idx[self.process_index * self.per_host:
                                   (self.process_index + 1) * self.per_host]
            batch = self.dataset[local_idx]
            valid = np.zeros(self.global_batch_size, np.int32)
            valid[:valid_n] = 1
            batch["valid"] = valid[self.process_index * self.per_host:
                                   (self.process_index + 1) * self.per_host]
            if self.bucket_sizes:
                batch = self._trim_to_buckets(batch, global_idx[:valid_n])
            yield batch

    # -- length bucketing (the tf.data bucket_by_sequence_length capability
    #    the reference forgoes by padding everything to 512,
    #    scripts/train.py:80-83) ------------------------------------------

    def _bucket_for(self, max_len: int, full: int) -> int:
        for b in self.bucket_sizes:
            if b >= max_len:
                return min(b, full)
        return full

    def _length_sorted_windows(self, order: np.ndarray) -> np.ndarray:
        """Sort by length inside windows of ``bucket_window`` batches: like
        batches get like lengths (less padding waste) while the epoch stays
        approximately shuffled. Deterministic — every host agrees."""
        key = self._lengths.get("attention_mask")
        if key is None or not self.shuffle:
            return order
        w = max(1, self.bucket_window) * self.global_batch_size
        out = order.copy()
        for lo in range(0, len(order), w):
            window = out[lo:lo + w]
            window.sort(kind="stable")  # determinism of ties
            out[lo:lo + w] = window[np.argsort(key[window], kind="stable")]
        return out

    def _trim_to_buckets(self, batch: dict[str, np.ndarray],
                         real_idx: np.ndarray) -> dict[str, np.ndarray]:
        """Slice token-width column groups down to the smallest bucket that
        holds the GLOBAL batch's longest row (all hosts agree: bucket
        choice derives from the shared order), so XLA compiles once per
        bucket size instead of padding every batch to the full width."""
        # ladder cap (ROADMAP "Compile-time budget"): once the run's
        # cumulative XLA compile time exceeds HSTD_COMPILE_BUDGET_S, stop
        # minting NEW batch shapes — widen to the smallest width this
        # batcher already emitted (already compiled), falling back to the
        # full column width. Single-host: acts the instant the local
        # tracker crosses. Multi-host: acts on the epoch-boundary
        # AGREED latch (trainer runs parallel.distributed.
        # agree_compile_budget_crossed and calls obs.
        # set_compile_budget_agreed on every host together), because the
        # budget is crossed at a host-local instant and bucket choices
        # must agree across hosts.
        capped = obs.compile_budget_capped(self.process_count)
        trims: dict[int, int] = {}  # original width -> bucket width
        for mask_name, lengths in self._lengths.items():
            width = self.dataset.columns[mask_name].shape[1]
            max_len = int(lengths[real_idx].max()) if len(real_idx) else 1
            bucket = self._bucket_for(max(max_len, 1), width)
            used = self._used_buckets.setdefault(mask_name, set())
            if capped and bucket not in used:
                bucket = min((b for b in used if b >= bucket),
                             default=width)
            # encoder/decoder columns with the SAME width share one trim:
            # take the safer (wider) bucket
            trims[width] = max(trims.get(width, 0), bucket)
        for mask_name in self._lengths:
            # record the APPLIED trim (post max-across-shared-widths) —
            # a pre-max per-mask bucket may never actually be emitted,
            # and treating it as "already compiled" would let the capped
            # ladder mint a fresh shape later
            width = self.dataset.columns[mask_name].shape[1]
            self._used_buckets.setdefault(mask_name, set()).add(
                trims[width])
        out = {}
        for k, v in batch.items():
            if v.ndim >= 2 and v.shape[1] in trims:
                out[k] = np.ascontiguousarray(v[:, :trims[v.shape[1]]])
            else:
                out[k] = v
        return out

    def global_arrays(self, epoch: int = 0, start_step: int = 0,
                      prefetch: Union[int, str] = "auto"):
        """Yield batches as globally-sharded jax.Arrays on the mesh.

        Token-dimension columns additionally shard over the ``seq`` axis
        when the mesh has one (sequence parallelism). The returned
        iterator has ``close()`` for early exit.

        ``prefetch="auto"`` (the default): host-side gather/tokenize runs
        on a background thread whose queue depth is AUTOTUNED from the
        live producer-wait/consumer-wait ratio (``data/autotune.py``;
        ``HSTD_PREFETCH_AUTOTUNE=0`` pins the pre-autotune depth 2), and
        host→device transfer is double-buffered on the consumer side
        (:class:`H2DStager`): batch N+1's ``device_put`` dispatches while
        the device computes on batch N — the tf.data prefetch the
        reference gets for free (``scripts/train.py:84-86``).

        ``prefetch=N`` keeps the fixed-depth behavior (transfer on the
        producer thread); ``prefetch=0`` disables the thread entirely.
        """
        if prefetch == "auto":
            seed_depth = {}
            if self._auto_tuner is not None:
                # carry the converged depth across epochs: the waits the
                # controller already paid to learn it are not re-paid
                seed_depth = {"initial_depth": self._auto_tuner.depth}
            tuner = PrefetchAutotuner.from_env(**seed_depth)
            if tuner is not None:
                self._auto_tuner = tuner
            host_it = PrefetchIterator(self.local_batches(epoch, start_step),
                                       depth=2, autotuner=tuner)
            return H2DStager(host_it, self._put_batch)
        it = self._device_batches(epoch, start_step)
        if prefetch > 0:
            return PrefetchIterator(it, depth=prefetch)
        return it

    def _put_batch(self, batch: dict[str, np.ndarray]) -> dict[str, jax.Array]:
        """One host batch → globally-sharded device arrays (the mesh
        helpers in ``parallel/sharding.py`` decide each column's spec)."""
        if self._count_tokens:
            am = batch.get("attention_mask")
            if am is not None:
                tok = int(np.count_nonzero(am))
            elif "input_ids" in batch:
                tok = int(batch["input_ids"].size)
            else:
                tok = 0
            dm = batch.get("decoder_attention_mask")
            dec = int(np.count_nonzero(dm)) if dm is not None else 0
            self.token_log.append((tok, dec))
        with obs.span("data/host_to_device"):
            return {
                k: jax.make_array_from_process_local_data(
                    self._column_sharding(v), v)
                for k, v in batch.items()
            }

    def _device_batches(self, epoch: int, start_step: int) -> Iterator[dict[str, jax.Array]]:
        for batch in self.local_batches(epoch, start_step):
            # _put_batch's span closes BEFORE the yield: a generator
            # suspended inside the with-block would bill consumer
            # think-time to the span
            yield self._put_batch(batch)

    def _column_sharding(self, v: np.ndarray) -> NamedSharding:
        key = (v.ndim, v.shape[1] if v.ndim >= 2 else None)
        sharding = self._sharding_cache.get(key)
        if sharding is None:
            sharding = batch_column_sharding(self.mesh, *key)
            self._sharding_cache[key] = sharding
        return sharding
