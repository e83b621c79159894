"""Length-bucketed throughput vs pad-to-512 (bench.py --buckets).

The reference densifies every example to the full 512-token width before
batching (reference ``scripts/train.py:80-83``), so short reviews pay
full-length compute. Our pipeline can bucket batches to the smallest
width multiple that fits the longest row (``ShardedBatcher``
bucket_sizes, ``data/pipeline.py``), trading a handful of extra XLA
compilations (one per width actually seen, amortized by the persistent
compilation cache) for proportionally less matmul work.

This mode trains the headline BERT-base config twice on the SAME
realistic length distribution — uniform 50-600 words, approximating
IMDb's wide spread around a ~230-word median — once padded to 512,
once bucketed at multiples of 128, and reports the bucketed throughput
with ``vs_baseline`` = bucketed ÷ padded (the win from not computing
padding). Both runs get a warmup epoch so every bucket width is
compiled before measurement.
"""

from __future__ import annotations


def bench_buckets() -> None:
    from bench import _on_tpu, emit, run_finetune

    # batch 48 is the headline's padded config — the padded baseline
    # must run at ITS best, or the bucketing win is inflated
    # by the baseline's self-inflicted spills
    kwargs = dict(model_kwargs={}, per_chip_batch=48 if _on_tpu() else 8,
                  min_len=50, max_len=600, batches=14, warmup_epochs=1)
    padded = run_finetune(**kwargs)
    bucketed = run_finetune(bucket_multiple=128, **kwargs)
    emit("bert_base_bucketed_samples_per_sec_per_chip",
         bucketed["train_samples_per_second_per_chip"],
         padded["train_samples_per_second_per_chip"])


if __name__ == "__main__":
    import os
    import sys

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))  # repo root, for `from bench import ...`
    bench_buckets()
