"""Streaming-tier demonstration: MLM pretraining over a corpus whose
materialized form would dwarf the dataset's resident footprint.

Generates a synthetic jsonl corpus on disk (size set by --rows), then
trains MLM for --steps steps through ``StreamingTextDataset`` +
``ShardedBatcher`` on the virtual CPU mesh, reporting:

- corpus file size and row count
- dataset resident bytes (the offset index — all the streaming tier pins)
- the bytes the materialized ``ArrayDataset`` equivalent would pin
  (3 int32 columns x [N, max_len])
- peak process RSS over the run

Run:  JAX_PLATFORMS=cpu \
      XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python benchmarks/streaming_demo.py --rows 200000 --steps 30

Why: stop replicating the reference's materialize-everything quirk
(reference ``scripts/train.py:80-83``)."""

from __future__ import annotations

import argparse
import json
import os
import resource
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=200_000)
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--max_len", type=int, default=512)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--path", default="/tmp/streaming_demo_corpus.jsonl")
    args = ap.parse_args()

    from huggingface_sagemaker_tensorflow_distributed_tpu.config import TrainConfig
    from huggingface_sagemaker_tensorflow_distributed_tpu.data import (
        LineCorpus,
        ShardedBatcher,
        StreamingTextDataset,
        WordHashTokenizer,
    )
    from huggingface_sagemaker_tensorflow_distributed_tpu.models.auto import init_params
    from huggingface_sagemaker_tensorflow_distributed_tpu.models.bert import (
        BertForMaskedLM,
    )
    from huggingface_sagemaker_tensorflow_distributed_tpu.models.layers import (
        EncoderConfig,
    )
    from huggingface_sagemaker_tensorflow_distributed_tpu.parallel import (
        MeshConfig,
        build_mesh,
    )
    from huggingface_sagemaker_tensorflow_distributed_tpu.train import Trainer

    # -- corpus on disk (generated once; ~870 bytes/row at the default
    #    150-word rows). One LineCorpus build doubles as the freshness
    #    check — no second full-file scan.
    corpus = LineCorpus(args.path) if os.path.exists(args.path) else None
    if corpus is None or len(corpus) != args.rows:
        rng = np.random.default_rng(0)
        words = ("the a of in on movie film plot actor scene story great "
                 "terrible fine sharp dull rich weak bright dark long short "
                 "first last early late director camera script character "
                 "moment ending opening").split()
        t0 = time.time()
        with open(args.path + ".tmp", "w") as f:
            for _ in range(args.rows):
                n = int(rng.integers(120, 180))
                text = " ".join(rng.choice(words, n))
                f.write(json.dumps({"text": text}) + "\n")
        os.replace(args.path + ".tmp", args.path)
        print(f"corpus generated in {time.time() - t0:.1f}s")
        corpus = LineCorpus(args.path)
    file_mb = os.path.getsize(args.path) / 1e6
    tok = WordHashTokenizer(vocab_size=8192)
    ds = StreamingTextDataset(corpus, tok, task="mlm",
                              max_length=args.max_len)
    resident = ds.resident_bytes()
    materialized = 3 * args.rows * args.max_len * 4  # ids/mask/labels int32

    mesh = build_mesh(MeshConfig())
    mcfg = EncoderConfig(vocab_size=8192, hidden_size=128, num_layers=2,
                         num_heads=4, intermediate_size=512,
                         max_position_embeddings=args.max_len,
                         use_pooler=False)
    model = BertForMaskedLM(mcfg)
    # two epochs of steps/2 so the history carries a trajectory (fit's
    # history is per-epoch means — one epoch would make first == final)
    cfg = TrainConfig(task="mlm", dtype="float32", learning_rate=3e-4,
                      scale_lr_by_world_size=False, log_every_steps=0,
                      epochs=2, steps_per_epoch=max(args.steps // 2, 1))
    trainer = Trainer(cfg, model, init_params(model, mcfg), mesh)
    batcher = ShardedBatcher(ds, args.batch, mesh, shuffle=True, seed=0)
    t0 = time.time()
    hist = trainer.fit(batcher)
    wall = time.time() - t0
    peak_rss = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024

    print(json.dumps({
        "rows": args.rows,
        "corpus_file_mb": round(file_mb, 1),
        "dataset_resident_bytes": resident,
        "materialized_equivalent_bytes": materialized,
        "resident_ratio": round(materialized / max(resident, 1)),
        "peak_rss_mb": round(peak_rss / 1e6, 1),
        "steps": args.steps,
        "final_loss": round(float(hist["loss"][-1]), 4),
        "first_loss": round(float(hist["loss"][0]), 4),
        "wall_s": round(wall, 1),
    }))


if __name__ == "__main__":
    main()
