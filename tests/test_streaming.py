"""Streaming data tier: ArrayDataset-equivalence, batch-composition
independence of MLM masking, bounded resident memory, and the CLI path.

The reference materializes its whole dataset densely in host memory
(reference ``scripts/train.py:80-83``); this tier replaces that with a
line-offset index + per-batch tokenization (SURVEY.md §2 quirk fix)."""

import json

import numpy as np
import pytest

from huggingface_sagemaker_tensorflow_distributed_tpu.data import (
    ArrayDataset,
    LineCorpus,
    ShardedBatcher,
    StreamingTextDataset,
    WordHashTokenizer,
)
from huggingface_sagemaker_tensorflow_distributed_tpu.data.sources import (
    synthetic_text_classification,
)
from huggingface_sagemaker_tensorflow_distributed_tpu.parallel import (
    MeshConfig,
    build_mesh,
)

SEQ = 32


def _write_jsonl(path, texts, labels=None):
    with open(path, "w") as f:
        for i, t in enumerate(texts):
            rec = {"text": t}
            if labels is not None:
                rec["label"] = int(labels[i])
            f.write(json.dumps(rec) + "\n")
    return str(path)


@pytest.fixture()
def corpus_file(tmp_path):
    texts, labels = synthetic_text_classification(64, seed=0)
    return _write_jsonl(tmp_path / "train.jsonl", texts, labels), texts, labels


def test_line_corpus_random_access(corpus_file):
    path, texts, labels = corpus_file
    corpus = LineCorpus(path)
    assert len(corpus) == len(texts)
    idx = np.array([5, 0, 63, 5])
    got, lab = corpus.read_rows(idx)
    assert got == [texts[5], texts[0], texts[63], texts[5]]
    assert lab == [labels[5], labels[0], labels[63], labels[5]]


def test_line_corpus_txt_and_trailing_newline(tmp_path):
    p = tmp_path / "c.txt"
    p.write_text("alpha beta\ngamma\ndelta epsilon\n")
    corpus = LineCorpus(str(p))
    assert len(corpus) == 3
    got, lab = corpus.read_rows(np.array([2, 0]))
    assert got == ["delta epsilon", "alpha beta"] and lab is None


def test_streaming_causal_lm_matches_materialized(corpus_file):
    """causal-lm has no randomness: streaming and materialized must
    produce bit-identical batches from the same ShardedBatcher seed —
    hence identical loss curves at equal data, checked at the strictest
    level."""
    path, texts, _ = corpus_file
    tok = WordHashTokenizer(vocab_size=512)
    mesh = build_mesh(MeshConfig())
    mat = ArrayDataset.from_lm_texts(tok, texts, max_length=SEQ)
    stream = StreamingTextDataset(LineCorpus(path), tok, task="causal-lm",
                                  max_length=SEQ)
    assert len(stream) == len(mat)
    for epoch in (0, 1):
        b_mat = list(ShardedBatcher(mat, 16, mesh, shuffle=True,
                                    seed=7).local_batches(epoch))
        b_str = list(ShardedBatcher(stream, 16, mesh, shuffle=True,
                                    seed=7).local_batches(epoch))
        assert len(b_mat) == len(b_str)
        for a, b in zip(b_mat, b_str):
            for k in a:
                np.testing.assert_array_equal(a[k], b[k], err_msg=k)


def test_streaming_seq_cls_matches_materialized(corpus_file):
    path, texts, labels = corpus_file
    tok = WordHashTokenizer(vocab_size=512)
    mat = ArrayDataset.from_texts(tok, texts, labels, max_length=SEQ)
    stream = StreamingTextDataset(LineCorpus(path), tok, task="seq-cls",
                                  max_length=SEQ)
    idx = np.arange(16)
    a, b = mat[idx], stream[idx]
    for k in ("input_ids", "attention_mask", "labels"):
        np.testing.assert_array_equal(a[k], b[k], err_msg=k)


def test_streaming_mlm_batch_composition_independent(corpus_file):
    """A row's masks depend only on (seed, epoch, row) — gathering it in
    different batches, alone, or in different order must not change
    them. This is what makes the shared epoch permutation sufficient for
    multi-host agreement without communication."""
    path, _, _ = corpus_file
    tok = WordHashTokenizer(vocab_size=512)
    stream = StreamingTextDataset(LineCorpus(path), tok, task="mlm",
                                  max_length=SEQ, seed=11)
    a = stream[np.arange(0, 8)]
    b = stream[np.array([3])]
    np.testing.assert_array_equal(a["input_ids"][3], b["input_ids"][0])
    np.testing.assert_array_equal(a["labels"][3], b["labels"][0])
    c = stream[np.array([7, 3, 0])]
    np.testing.assert_array_equal(c["input_ids"][1], b["input_ids"][0])
    # epoch changes the draw; determinism within an epoch
    stream.begin_epoch(1)
    d = stream[np.array([3])]
    assert (d["labels"] != b["labels"]).any()
    stream.begin_epoch(0)
    e = stream[np.array([3])]
    np.testing.assert_array_equal(e["labels"], b["labels"])


def test_streaming_mlm_statistics(corpus_file):
    path, _, _ = corpus_file
    tok = WordHashTokenizer(vocab_size=512)
    stream = StreamingTextDataset(LineCorpus(path), tok, task="mlm",
                                  max_length=SEQ, seed=0)
    batch = stream[np.arange(64)]
    masked = batch["labels"] != -100
    frac = masked.sum() / (batch["attention_mask"].sum() - 2 * 64)
    assert 0.06 < frac < 0.3
    mask_frac = (batch["input_ids"][masked] == tok.mask_token_id).mean()
    assert 0.6 < mask_frac < 0.95


def test_streaming_resident_memory_is_offsets_only(tmp_path):
    """The streaming dataset pins ~8 bytes/row regardless of text size;
    the materialized equivalent pins the full padded [N, L] columns.
    At 512 tokens that's a ~250x gap — the corpus-larger-than-RAM
    property at test scale."""
    texts, labels = synthetic_text_classification(256, seed=1)
    path = _write_jsonl(tmp_path / "t.jsonl", texts, labels)
    tok = WordHashTokenizer(vocab_size=512)
    stream = StreamingTextDataset(LineCorpus(path), tok, task="mlm",
                                  max_length=512)
    mat = ArrayDataset.from_mlm_texts(tok, texts, max_length=512)
    mat_bytes = sum(v.nbytes for v in mat.columns.values())
    assert stream.resident_bytes() < mat_bytes / 100
    assert stream.resident_bytes() == (256 + 1) * 8


def test_streaming_rejects_buckets_and_bad_tasks(corpus_file):
    path, _, _ = corpus_file
    tok = WordHashTokenizer(vocab_size=512)
    stream = StreamingTextDataset(LineCorpus(path), tok, task="mlm",
                                  max_length=SEQ)
    mesh = build_mesh(MeshConfig())
    with pytest.raises(ValueError, match="bucket"):
        ShardedBatcher(stream, 16, mesh, bucket_sizes=[16, 32])
    with pytest.raises(ValueError, match="streaming tier supports"):
        StreamingTextDataset(LineCorpus(path), tok, task="qa")


def test_streaming_seq2seq_rejects_txt_corpus(tmp_path):
    """A .txt corpus has no source/target fields: fail at construction,
    not minutes later at the first batch."""
    p = tmp_path / "c.txt"
    p.write_text("one\ntwo\n")
    tok = WordHashTokenizer(vocab_size=512)
    with pytest.raises(ValueError, match="jsonl"):
        StreamingTextDataset(LineCorpus(str(p)), tok, task="seq2seq")


def test_streaming_cli_mlm(tmp_path, devices8):
    """scripts/train.py --streaming true trains MLM end to end from a
    disk corpus and writes the same results contract."""
    import transformers

    from scripts.train import main as train_main

    texts, labels = synthetic_text_classification(128, seed=0)
    data_dir = tmp_path / "data"
    data_dir.mkdir()
    _write_jsonl(data_dir / "train.jsonl", texts, labels)
    _write_jsonl(data_dir / "test.jsonl", texts[:32], labels[:32])
    mdir = str(tmp_path / "cfg")
    transformers.BertConfig(
        vocab_size=4096, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=2, intermediate_size=64,
        max_position_embeddings=SEQ).save_pretrained(mdir)
    out = str(tmp_path / "out")
    train_main([
        "--task", "mlm", "--dataset_path", str(data_dir),
        "--streaming", "true", "--from_scratch", "true",
        "--model_name_or_path", mdir, "--epochs", "1",
        "--train_batch_size", "2", "--dtype", "float32",
        "--max_seq_length", str(SEQ), "--learning_rate", "1e-3",
        "--scale_lr_by_world_size", "false",
        "--output_data_dir", out, "--model_dir", str(tmp_path / "model"),
    ])
    text = (tmp_path / "out" / "train_results.txt").read_text()
    assert "train_runtime" in text and "loss" in text


def test_streaming_seq2seq_matches_materialized(tmp_path):
    """seq2seq streaming encodes each batch through the SAME from_seq2seq
    builder — bit-identical columns to the materialized dataset."""
    from huggingface_sagemaker_tensorflow_distributed_tpu.data.sources import (
        synthetic_summarization,
    )

    sources, targets = synthetic_summarization(32, seed=2)
    path = tmp_path / "train.jsonl"
    with open(path, "w") as f:
        for s, t in zip(sources, targets):
            f.write(json.dumps({"source": s, "target": t}) + "\n")
    tok = WordHashTokenizer(vocab_size=512)
    kw = dict(max_target_length=12, decoder_start_token_id=0,
              pad_token_id=0, eos_token_id=1)
    mat = ArrayDataset.from_seq2seq(tok, sources, targets,
                                    max_source_length=SEQ, **kw)
    stream = StreamingTextDataset(LineCorpus(str(path)), tok,
                                  task="seq2seq", max_length=SEQ,
                                  seq2seq_kwargs=kw)
    assert len(stream) == len(mat)
    idx = np.array([5, 0, 31, 17])
    a, b = mat[idx], stream[idx]
    assert set(a) == set(b)
    for k in a:
        np.testing.assert_array_equal(a[k], b[k], err_msg=k)


def test_native_line_boundaries_matches_python(tmp_path):
    """The C++ pread+memchr indexer and the Python line loop build the
    IDENTICAL boundary array — with and without a trailing newline, and
    with CRLF rows (skips when no toolchain: the fallback IS the loop)."""
    from huggingface_sagemaker_tensorflow_distributed_tpu.data.native import (
        native_line_boundaries,
    )

    cases = {
        "lf.jsonl": b'{"text": "a"}\n{"text": "bb"}\n{"text": "ccc"}\n',
        "no_trail.txt": b"alpha\nbeta\ngamma",
        "crlf.txt": b"one\r\ntwo\r\nthree\r\n",
        "empty.txt": b"",
    }
    for name, payload in cases.items():
        p = tmp_path / name
        p.write_bytes(payload)
        native = native_line_boundaries(str(p))
        if native is None:
            pytest.skip("no native toolchain")
        offsets = [0]
        with open(p, "rb") as f:
            for line in f:
                offsets.append(offsets[-1] + len(line))
        np.testing.assert_array_equal(native, np.asarray(offsets, np.int64),
                                      err_msg=name)
