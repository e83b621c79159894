"""Benchmarks on the real jitted training path (same code as
``scripts/train.py``).

Default (no args) — the headline metric, ONE JSON line:
BERT-base fine-tune, seq 512, bf16, Pallas flash attention, per-chip
batch 48 — the reference's default workload shape (BERT-family, IMDb
padded to 512; reference ``launch.py:13-18``, ``scripts/train.py:81-86``)
on synthetic IMDb-shaped data (zero-egress environment). The reference
pins batch 8/worker; per-chip batch is a free throughput knob here, and
48 was chosen on a v5e in an earlier round (a profiler trace showed
batch 64 pushing HBM into XLA spill copies); that sweep predates the
installed jax and has not been measured on this code.

``vs_baseline``: the reference publishes no numbers (BASELINE.md), so the
comparison point is the reference's default hardware envelope — BERT-base
fine-tuning at seq 512 / batch 8 / mixed precision on the ml.p3.2xlarge
V100, ≈32 samples/s (public MLPerf-era V100 BERT fine-tune throughput);
vs_baseline = our samples/sec/chip ÷ 32.

The line also carries FLOPs accounting: analytic matmul FLOPs/sample for
the benched model (fwd ≈ 2·N·tokens for the matmuls, train ≈ 3× fwd —
the standard model-FLOPs convention, which excludes remat recompute),
achieved TFLOP/s/chip, and MFU against the chip's bf16 peak.

One process holds the chip (the reference's self-measurement contract
is the ``train_runtime`` history emission around ``fit``, reference
``scripts/train.py:142,154-165``; ours must also survive being killed):
the parent process NEVER initializes a JAX backend. It runs the measured
bench in one supervised child with a hard timeout — the first and only
process that opens the backend, which reports the device itself — and
forwards the child's lines as they arrive, flushed, stamping every
metric line with ``platform``, ``device_kind`` and ``device_count``.
The kernel-parity subset runs strictly after that child has exited.
A run that has no value to print — no TPU and the CPU not asked for by
name, child crash, child hang, failed parity — prints ONE structured
JSON line per metric (``"error": ...``) and exits non-zero.

Extra modes (each also prints one JSON line per run):
  --model bert-large   the reference's actual default model
                       (bert-large-uncased-whole-word-masking shape:
                       24L/1024H/16 heads; reference ``launch.py:17``),
                       seq 512, per-chip batch 8.
  --buckets            headline workload with length bucketing enabled
                       on a realistic length distribution (vs pad-to-512).
  --mesh               scaling-efficiency instrument: per-step collective
                       vs compute time from a profiler trace.
  --generate           decode throughput: tokens/s/chip for GPT-2
                       prefill+scan and BART cached greedy + beam.
  --causal-lm          GPT-2 124M training throughput, fused
                       vocab-CE loss vs full-logits baseline.
  --mlm                BERT-base WWM pretraining throughput, sparse-
                       gather fused vocab-CE vs full-logits baseline.
  --lora               BERT-large + LoRA r=8: the frozen base carries no
                       Adam m/v or grad tree, buying per-chip batch 32
                       (full fine-tuning's HBM sweet spot is 8-16).
  --banded             banded-flash microbench: sliding-window vs full
                       causal fwd+bwd at seq 8192 (the O(S*window)
                       tile-skip claim, measured).
  --llama-train        TinyLlama-1.1B causal-LM training on one chip
                       (bf16 Adam + remat dots + fused vocab-CE +
                       flash), samples/s + MFU.
  --serve              continuous-batching serving engine (serve/:
                       paged KV + iteration-level scheduling) vs
                       static-batch generate_causal on a mixed-length
                       request trace (speedup, TTFT p50/p99, KV-pool
                       utilization, compile-flatness check), plus the
                       width-bucketed gather line: bucketed vs
                       full-width decode tokens/sec on a short-context
                       trace (>=1.3x CPU gate, identical outputs,
                       compiles <= #buckets), the speculative-decode
                       line (>=1.5x CPU gate), the prefix-cache
                       line: TTFT p50 with copy-on-write prefix
                       caching on vs off on a repeated-prefix trace
                       (>=2x CPU gate, identical outputs, block
                       conservation), the paged-kernel line:
                       int8 vs fp KV pools on a decode-dominated
                       trace (>=1.2x CPU gate, per-side exactness,
                       per-step pool bytes <=0.6x asserted), and the
                       tensor-parallel capacity line: TP=2 vs TP=1 on
                       the same per-device KV byte budget (>=2x
                       admission depth, <=0.55x per-device pool
                       bytes/token, token identity — all
                       deterministic gates).

Every metric line additionally carries a ``memory`` watermark field on
accelerator backends (peak_bytes_in_use vs bytes_limit, ROADMAP "Memory
watermarks") so HBM-spill regressions surface next to the throughput
they cost, plus an ``anomalies`` count from the run's anomaly detector
(``obs/anomaly.py``; zero on healthy runs). MFU rides on every training
line — on TPU from the peak table, elsewhere only under an explicit
``HSTD_PEAK_TFLOPS`` override. A measured body whose training loss went
non-finite exits ``ANOMALY_RC`` (3) AFTER printing its lines, so CI
catches silent divergence (every other failure exits 1).
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import threading
import time

_REPO_ROOT = os.path.dirname(os.path.abspath(__file__))

V100_BASELINE_SAMPLES_PER_SEC = 32.0
# BERT-large at seq 512 / bs 8 / mixed precision on one V100 runs ≈1/4 of
# BERT-base throughput — public MLPerf-era fine-tune numbers put it ≈8
# samples/s; same caveat as above: a literature anchor, not a measurement.
V100_BERT_LARGE_SAMPLES_PER_SEC = 8.0

BERT_LARGE = dict(hidden_size=1024, num_layers=24, num_heads=16,
                  intermediate_size=4096)


def chip_peak_tflops(device_kind: str) -> float | None:
    """Peak bf16 TFLOP/s for the chip — one source of truth in
    ``obs/flops.py`` (device_kind table + ``HSTD_PEAK_TFLOPS`` env
    override for chips the table doesn't know, CPU included)."""
    from huggingface_sagemaker_tensorflow_distributed_tpu.obs.flops import (
        peak_tflops,
    )

    return peak_tflops(device_kind)


def train_flops_per_sample(seq_len: int, hidden_size: int = 768,
                           num_layers: int = 12,
                           intermediate_size: int = 3072) -> float:
    """Analytic matmul FLOPs for ONE training sample (fwd+bwd) of a
    BERT-family encoder. Delegates to the ONE FLOPs convention in
    ``obs/flops.py`` (3× forward; remat recompute excluded; embedding
    lookups / layernorms / softmax excluded, ~2% at these shapes) so
    bench-line MFU and trainer-history MFU can never drift."""
    import types

    from huggingface_sagemaker_tensorflow_distributed_tpu.obs.flops import (
        train_flops_per_token,
    )

    cfg = types.SimpleNamespace(hidden_size=hidden_size,
                                num_layers=num_layers,
                                intermediate_size=intermediate_size,
                                vocab_size=0)
    return seq_len * train_flops_per_token(cfg, "seq-cls", seq_len)


def build_harness(model_kwargs: dict, per_chip_batch: int, seq_len: int = 512,
                  remat: bool = False, remat_policy: str = "full",
                  bucket_multiple: int = 0,
                  min_len: int = 300, max_len: int = 600, batches: int = 14,
                  opt_state_bf16: bool = False, lora_rank: int = 0,
                  lora_targets: str = "attention"):
    """(trainer, batcher) for one BERT-family benchmark config — the ONE
    place every bench mode builds its harness, so --mesh/--buckets always
    measure the same configuration the headline does."""
    import jax
    import jax.numpy as jnp

    from huggingface_sagemaker_tensorflow_distributed_tpu.config import TrainConfig
    from huggingface_sagemaker_tensorflow_distributed_tpu.data import (
        ArrayDataset,
        ShardedBatcher,
        WordHashTokenizer,
    )
    from huggingface_sagemaker_tensorflow_distributed_tpu.data.sources import (
        synthetic_text_classification,
    )
    from huggingface_sagemaker_tensorflow_distributed_tpu.models.auto import init_params
    from huggingface_sagemaker_tensorflow_distributed_tpu.models.bert import (
        BertForSequenceClassification,
    )
    from huggingface_sagemaker_tensorflow_distributed_tpu.models.layers import EncoderConfig
    from huggingface_sagemaker_tensorflow_distributed_tpu.parallel import (
        MeshConfig,
        build_mesh,
    )
    from huggingface_sagemaker_tensorflow_distributed_tpu.train import Trainer

    n_chips = len(jax.devices())
    on_tpu = jax.devices()[0].platform == "tpu"
    global_batch = per_chip_batch * n_chips

    mesh = build_mesh(MeshConfig(dp=-1))
    config = TrainConfig(dtype="bfloat16" if on_tpu else "float32",
                         train_batch_size=per_chip_batch,
                         max_seq_length=seq_len, log_every_steps=0,
                         remat=remat, bucket_multiple=bucket_multiple,
                         optimizer_state_dtype="bfloat16" if opt_state_bf16
                         else "float32", lora_rank=lora_rank,
                         lora_targets=lora_targets)
    model_cfg = EncoderConfig(
        dtype=jnp.bfloat16 if on_tpu else jnp.float32,
        max_position_embeddings=512,
        attention_impl=config.resolve_attention_impl(
            jax.devices()[0].platform),
        remat=remat, remat_policy=remat_policy,
        **model_kwargs)
    model = BertForSequenceClassification(model_cfg, num_labels=2)
    params = init_params(model, model_cfg, seed=0)
    trainer = Trainer(config, model, params, mesh)

    tok = WordHashTokenizer()
    texts, labels = synthetic_text_classification(
        global_batch * batches, seed=0, min_len=min_len, max_len=max_len)
    ds = ArrayDataset.from_texts(tok, texts, labels, max_length=seq_len)
    batcher = ShardedBatcher(ds, global_batch, mesh, shuffle=False, seed=0,
                             bucket_sizes=config.bucket_sizes(seq_len))
    return trainer, batcher


def run_finetune(model_kwargs: dict, per_chip_batch: int,
                 epochs: int = 2, warmup_epochs: int = 0, **harness_kwargs):
    """Train-loop throughput for one BERT-family config; returns the fit
    history (the meter excludes the first, compiling, step and runs the
    REAL fit loop: async dispatch, background prefetch, no per-step host
    sync). ``warmup_epochs`` runs an unmeasured fit first so every bucket
    width compiles before the measured pass (the meter only skips the
    first step, which covers a single static shape)."""
    trainer, batcher = build_harness(model_kwargs, per_chip_batch,
                                     **harness_kwargs)
    if warmup_epochs:
        trainer.fit(batcher, epochs=warmup_epochs)
    return trainer.fit(batcher, epochs=epochs)


def _flops_detail(samples_per_sec_per_chip: float,
                  flops_per_sample: float) -> dict:
    """TFLOP/s/chip + MFU fields for an emit line. MFU is null when the
    chip's peak is unknown; on CPU the ``HSTD_PEAK_TFLOPS`` override is
    the only way to get one (the obsctl acceptance path uses it)."""
    import jax

    achieved = samples_per_sec_per_chip * flops_per_sample / 1e12
    kind = jax.devices()[0].device_kind
    peak = chip_peak_tflops(kind)
    if peak is None and _on_tpu():
        raise LookupError(f"device_kind {kind!r} is not in the peaks "
                          "table of obs/flops.py: add it with its source")
    return {
        "model_tflops_per_sample": round(flops_per_sample / 1e12, 4),
        "achieved_tflops_per_chip": round(achieved, 4),
        "chip_peak_tflops": peak,
        "mfu": round(achieved / peak, 6) if peak else None,
    }


def _flops_reportable() -> bool:
    """Should a metric line carry FLOPs/MFU fields? Always on TPU;
    elsewhere only under an explicit ``HSTD_PEAK_TFLOPS`` (a guessed
    CPU peak would make MFU noise, not a metric)."""
    from huggingface_sagemaker_tensorflow_distributed_tpu.obs.flops import (
        env_peak_tflops,
    )

    return _on_tpu() or env_peak_tflops() is not None


def memory_watermark() -> dict | None:
    """Peak-vs-limit device-memory watermark across local devices
    (ROADMAP "Memory watermarks") — the figure that catches HBM-spill
    regressions like the batch-64 spill story without a profiler trace.
    None on CPU backends / before jax initializes (the supervisor
    parent never initializes a backend, so it must never call this
    successfully by accident)."""
    if "jax" not in sys.modules:
        return None
    jax = sys.modules["jax"]
    try:
        devices = jax.local_devices()
    except Exception:  # noqa: BLE001 — backend gone / not initialized
        return None
    peaks = []
    for d in devices:
        try:
            stats = d.memory_stats() or {}
        except Exception:  # noqa: BLE001 — CPU backends raise
            stats = {}
        if stats.get("peak_bytes_in_use"):
            peaks.append((int(stats["peak_bytes_in_use"]),
                          int(stats.get("bytes_limit") or 0)))
    if not peaks:
        return None
    peak = max(p for p, _ in peaks)
    limit = max((lim for _, lim in peaks if lim), default=0)
    out = {"peak_bytes_in_use": peak}
    if limit:
        out["bytes_limit"] = limit
        out["peak_frac"] = round(peak / limit, 3)
    return out


def anomaly_field() -> dict:
    """The ``anomalies`` field every metric line carries: total count +
    per-kind breakdown from the live detector (zero/empty on healthy
    runs — which is what CI greps for)."""
    from huggingface_sagemaker_tensorflow_distributed_tpu import obs

    counts = obs.anomaly_counts()
    return {"anomalies": sum(counts.values()), **(
        {"anomaly_kinds": counts} if counts else {})}


def emit(metric: str, value: float, baseline: float,
         flops_per_sample: float | None = None, **extra) -> None:
    line = {
        "metric": metric,
        "value": round(value, 3),
        "unit": "samples/sec/chip",
        "vs_baseline": round(value / baseline, 3),
    }
    if flops_per_sample is not None and _flops_reportable():
        line.update(_flops_detail(value, flops_per_sample))
    line.update(anomaly_field())
    mem = memory_watermark()
    if mem is not None:
        # every stage line carries the watermark: a spill regression
        # shows as peak_frac -> 1.0 next to the throughput it costs
        line["memory"] = mem
        print(f"[bench] memory watermark: peak {mem['peak_bytes_in_use']}"
              + (f" / limit {mem['bytes_limit']}"
                 f" ({mem['peak_frac']:.1%})" if "bytes_limit" in mem
                 else ""), file=sys.stderr)
    line.update(extra)
    print(json.dumps(line))


def _on_tpu() -> bool:
    import jax
    return jax.devices()[0].platform == "tpu"


def bench_headline(per_chip_batch: int | None = None,
                   opt_state_bf16: bool = False,
                   remat_policy: str | None = None) -> None:
    # batch 8 off-TPU keeps the CPU smoke run tractable
    if per_chip_batch is None:
        per_chip_batch = 48 if _on_tpu() else 8
    history = run_finetune({}, per_chip_batch=per_chip_batch,
                           opt_state_bf16=opt_state_bf16,
                           remat=remat_policy is not None,
                           remat_policy=remat_policy or "full")
    emit("bert_base_finetune_samples_per_sec_per_chip",
         history["train_samples_per_second_per_chip"],
         V100_BASELINE_SAMPLES_PER_SEC,
         flops_per_sample=train_flops_per_sample(512),
         detail={"per_chip_batch": per_chip_batch,
                 "optimizer_state_dtype":
                     "bfloat16" if opt_state_bf16 else "float32",
                 "remat_policy": remat_policy or "off"})


def _bert_large_flops_per_sample() -> float:
    """One source of truth for the BERT-large full-train FLOPs figure —
    both bert-large modes must report MFU under the same convention."""
    return train_flops_per_sample(512, **{
        k: v for k, v in BERT_LARGE.items() if k != "num_heads"})


def bench_lora() -> None:
    """BERT-large + LoRA r=8 (attention targets, trainable head): the
    base model's fp32 Adam m/v (2x 1.36G) and backbone grad tree vanish,
    so per-chip batch 32 — past full fine-tuning's HBM sweet spot of
    8-16 — runs without spills. Same measurement contract as the
    bert-large mode, so the samples/s and vs_baseline compare directly
    (baseline: the reference's full fine-tune on V100)."""
    batch = 32 if _on_tpu() else 1
    targets = "attention"
    history = run_finetune(BERT_LARGE, per_chip_batch=batch,
                           lora_rank=8, lora_targets=targets)
    # FLOPs convention: full fine-tune is ~3x forward (fwd + dX + dW);
    # with the backbone's dW matmuls dead-code-eliminated (stop-gradient
    # base, models/lora.py) the hardware executes ~2x forward, so MFU
    # must be computed against 2/3 of the full-train FLOPs — the 3x
    # figure would overstate utilization by ~1.5x
    full_flops = _bert_large_flops_per_sample()
    emit("bert_large_lora_r8_samples_per_sec_per_chip",
         history["train_samples_per_second_per_chip"],
         V100_BERT_LARGE_SAMPLES_PER_SEC,
         flops_per_sample=full_flops * 2.0 / 3.0,
         detail={"per_chip_batch": batch, "lora_rank": 8,
                 "lora_targets": targets,
                 "flops_convention": "fwd+dx only (no backbone dW)"})


def bench_bert_large() -> None:
    # the reference's default workload at its default size: bs 8/worker
    # (reference launch.py:13-18); 340M params + fp32 Adam state fit one
    # 16G chip without encoder remat
    history = run_finetune(BERT_LARGE, per_chip_batch=8 if _on_tpu() else 1)
    emit("bert_large_wwm_finetune_samples_per_sec_per_chip",
         history["train_samples_per_second_per_chip"],
         V100_BERT_LARGE_SAMPLES_PER_SEC,
         flops_per_sample=_bert_large_flops_per_sample())


# ---------------------------------------------------------------------------
# Supervisor (parent process; never initializes JAX)
# ---------------------------------------------------------------------------

def _default_budget() -> float | None:
    """Overall deadline for one bench invocation, settable without
    touching the driver's command line (``BENCH_BUDGET_SECONDS``). None
    leaves only the child's own 30 min timeout."""
    raw = os.environ.get("BENCH_BUDGET_SECONDS", "").strip()
    try:
        return float(raw) if raw else None
    except ValueError:
        return None


CHILD_TIMEOUT_S = int(os.environ.get("BENCH_TIMEOUT", "1800"))
PARITY_TIMEOUT_S = int(os.environ.get("BENCH_PARITY_TIMEOUT", "600"))
# exit code reserved for "measured fine but the run diverged" (NaN-loss
# anomaly): the child returns it, the supervisor propagates it
ANOMALY_RC = 3


def run_kernel_parity() -> dict:
    """Run the ~2-min compiled-kernel-parity subset in a supervised
    subprocess — strictly after the measured child has exited, so the
    chip has one holder at a time — and return a compact summary for
    the headline JSON line: throughput and kernel evidence in the same
    artifact. Never raises; the caller turns a failed or crashed subset
    into a non-zero exit after printing the line."""
    argv = [sys.executable,
            os.path.join(_REPO_ROOT, "benchmarks", "tpu_kernel_parity.py"),
            "--subset"]
    try:
        proc = subprocess.run(argv, cwd=_REPO_ROOT, stdout=subprocess.PIPE,
                              stderr=subprocess.STDOUT, text=True,
                              timeout=PARITY_TIMEOUT_S)
    except subprocess.TimeoutExpired:
        return {"error": f"timeout>{PARITY_TIMEOUT_S}s"}
    lines = proc.stdout.splitlines()
    passed = sum(1 for ln in lines if ln.startswith("PASS "))
    failed = [ln.split(":", 1)[0][5:] for ln in lines if ln.startswith("FAIL ")]
    summary = {"pass": passed, "fail": len(failed), "subset": True,
               "rc": proc.returncode}
    if failed:
        summary["failed"] = failed
    if proc.returncode == 2:
        summary["error"] = "no_evidence_not_tpu"
    elif proc.returncode != 0 and not failed:
        summary["error"] = "crashed"
        summary["tail"] = proc.stdout[-300:]
    return summary


def bench_lint() -> None:
    """The ``--lint`` stage: run graftlint over the tree and emit one
    ``lint_findings`` count line. Zero-baseline count semantics (shared
    with compiles/anomalies): the healthy value is 0, ANY unsuppressed
    finding is a regression, worse direction UP — which is exactly how
    ``obsctl diff`` gates the matching report scalar. Runs in-process
    (no jax, no supervised child: the linter is stdlib-only by rule
    R1), and mirrors the count into telemetry (``lint/findings``) when
    a sink is configured so ``obsctl report`` carries it."""
    from huggingface_sagemaker_tensorflow_distributed_tpu import obs
    from huggingface_sagemaker_tensorflow_distributed_tpu.analysis.lint import (
        LintInputError,
        run_lint,
    )

    root = os.path.dirname(os.path.abspath(__file__))
    try:
        result = run_lint(root)
    except LintInputError as e:
        emit_error(["lint_findings"], "lint_bad_input",
                   {"message": str(e)})
        sys.exit(1)
    n = len(result.active)
    if obs.has_sink():
        obs.scalar("lint/findings", n)
        obs.flush()
    print(json.dumps({
        "metric": "lint_findings", "value": n, "unit": "findings",
        "vs_baseline": None, "worse_direction": "up",
        "suppressed": len(result.suppressed),
        "per_rule": result.counts(),
        "detail": {"finding": [f.render() for f in result.active[:20]]}
        if n else {},
    }))


def emit_error(metrics: list[str], error: str, detail: dict) -> None:
    """The structured-failure contract: one parseable JSON line per
    metric the mode would have produced; the caller exits non-zero."""
    for metric in metrics:
        print(json.dumps({"metric": metric, "value": None, "unit": None,
                          "vs_baseline": None, "error": error,
                          "detail": detail}))


def _mode_metrics(args: argparse.Namespace) -> list[str]:
    """Exactly the metric names the mode emits on success, so error and
    success lines for one mode correlate by name."""
    if args.mesh:
        return ["train_step_collective_fraction"]
    if args.buckets:
        return ["bert_base_bucketed_samples_per_sec_per_chip"]
    if args.generate:
        return [f"generate_{m}_tokens_per_sec_per_chip"
                for m in ("gpt2_greedy", "gpt2_greedy_int8",
                          "llama_greedy", "llama_greedy_int8",
                          "llama_greedy_b1", "llama_self_spec_b1",
                          "bart_greedy", "bart_beam4")]
    if args.causal_lm:
        return ["gpt2_finetune_fused_ce_samples_per_sec_per_chip"]
    if args.mlm:
        return ["bert_base_mlm_fused_ce_samples_per_sec_per_chip"]
    if args.banded:
        return ["flash_banded_fwd_bwd_ms"]
    # getattr: test harnesses build Namespaces predating this flag
    if getattr(args, "data", False):
        return ["data_pipeline_microbench"]
    if getattr(args, "serve", False):
        return ["serve_continuous_vs_static_speedup",
                "serve_bucketed_gather_decode_speedup",
                "serve_speculative_decode_speedup",
                "serve_prefix_cache_ttft_speedup",
                "serve_paged_kernel_decode_speedup",
                "serve_overlap_decode_speedup",
                "serve_tp_shard_capacity",
                "serve_router_scaleout",
                "serve_open_loop_goodput"]
    if args.llama_train:
        return ["llama_1b_train_samples_per_sec_per_chip"]
    if args.mixtral_train:
        return ["mixtral_moe_train_samples_per_sec_per_chip"]
    if args.lora:
        return ["bert_large_lora_r8_samples_per_sec_per_chip"]
    if args.model == "bert-large":
        return ["bert_large_wwm_finetune_samples_per_sec_per_chip"]
    return ["bert_base_finetune_samples_per_sec_per_chip"]


def emit_provisional(metrics: list[str], stage: str, **extra) -> None:
    """One parseable JSON line marking progress: if the driver's own
    timeout kills this process at ANY point after startup, the last
    stdout line is already valid JSON naming the stage that was running
    — never an empty tail."""
    line = {"metric": metrics[0], "value": None, "unit": None,
            "vs_baseline": None, "provisional": True, "stage": stage}
    line.update(extra)
    print(json.dumps(line), flush=True)


def _cpu_asked_by_name() -> bool:
    """``JAX_PLATFORMS`` puts the CPU first — the one way a bench run
    may land on the CPU (a rehearsal at small size)."""
    first = os.environ.get("JAX_PLATFORMS", "").split(",")[0]
    return first.strip().lower() == "cpu"


def _parse_line(line: str) -> dict | None:
    try:
        rec = json.loads(line)
    except ValueError:
        return None
    return rec if isinstance(rec, dict) else None


def supervise(args: argparse.Namespace) -> int:
    """Run the measured bench in one supervised child — the first and
    only process to open the backend — forwarding its lines as they
    arrive; returns the exit code. The child's first line names the
    device, and every metric line is stamped with it. A child that
    crashes, hangs past its timeout or finds no TPU yields a structured
    error line and a non-zero code. With a budget (``--budget-seconds``
    / ``BENCH_BUDGET_SECONDS``) the child gets a deadline of its own
    and a timeout degrades to partial output, not an empty tail."""
    metrics = _mode_metrics(args)
    budget = args.budget_seconds
    deadline = time.monotonic() + budget if budget is not None else None
    # the measured child streams telemetry (events.jsonl + trace.json):
    # a run that dies mid-compile still leaves heartbeat/compile events
    child_env = dict(os.environ)
    child_env.setdefault("HSTD_TELEMETRY_DIR",
                         os.path.join(os.getcwd(), "telemetry"))
    emit_provisional(metrics, "measuring", budget_s=budget,
                     all_metrics=metrics)

    if (getattr(args, "serve", False) and _cpu_asked_by_name()
            and "xla_force_host_platform_device_count"
            not in child_env.get("XLA_FLAGS", "")):
        # the serve_tp_shard_capacity line shards an engine over 2
        # devices; a CPU host exposes 1 by default, so force a 2-device
        # host platform in the measured child (same mechanism the test
        # conftest uses — harmless to the single-device lines, which
        # keep placing everything on device 0)
        child_env["XLA_FLAGS"] = (
            child_env.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=2").strip()

    child_argv = [sys.executable, os.path.abspath(__file__),
                  *sys.argv[1:], "--_child"]
    child_timeout = CHILD_TIMEOUT_S
    if deadline is not None:
        # +10s grace: the child's own in-process alarm fires first and
        # emits partial JSON + flushes telemetry; this outer timeout only
        # catches a child wedged in native code where signals can't land
        remaining = max(deadline - time.monotonic(), 5)
        child_timeout = remaining + 10
        child_env["_BENCH_CHILD_BUDGET"] = str(round(remaining, 1))
    proc = subprocess.Popen(child_argv, cwd=_REPO_ROOT,
                            stdout=subprocess.PIPE, stderr=sys.stderr,
                            text=True, env=child_env)
    timed_out = threading.Event()

    def _kill():
        timed_out.set()
        proc.kill()

    timer = threading.Timer(child_timeout, _kill)
    timer.start()
    device: dict = {}
    last: dict | None = None        # the last metric line forwarded
    try:
        for raw in proc.stdout:
            line = raw.rstrip("\n")
            rec = _parse_line(line)
            if rec is not None and rec.get("stage") == "device":
                device = rec.get("device") or {}
            elif (rec is not None and "metric" in rec
                    and not rec.get("provisional")):
                rec.update({k: device.get(k) for k in
                            ("platform", "device_kind", "device_count")})
                line, last = json.dumps(rec), rec
            print(line, flush=True)
        rc = proc.wait()
    finally:
        timer.cancel()
    if timed_out.is_set():
        emit_error(metrics, "bench_timeout",
                   {"timeout_s": round(child_timeout, 1), "device": device})
        return 1
    if rc == ANOMALY_RC:
        # NaN-loss contract: the child measured and emitted real lines
        # (each carrying the anomalies field) but the run diverged
        print("[bench] NaN-loss anomaly: exiting nonzero", file=sys.stderr)
        return ANOMALY_RC
    if rc != 0:
        if last is None or "error" not in last:
            emit_error(metrics, "bench_failed", {"rc": rc, "device": device})
        return 1
    parity_affordable = (deadline is None
                         or deadline - time.monotonic() > PARITY_TIMEOUT_S)
    if (metrics == ["bert_base_finetune_samples_per_sec_per_chip"]
            and args.batch is None and not args.opt_state_bf16
            and args.remat_policy is None and parity_affordable
            and device.get("platform") == "tpu" and last is not None):
        # default (driver) invocation only: compiled-kernel-parity
        # evidence on the same line the driver records (the --batch /
        # --opt-state-bf16 sweep variants skip its ~2 min). The headline
        # is already on stdout; it is printed again with the field.
        print("[bench] running kernel-parity subset", file=sys.stderr)
        parity = run_kernel_parity()
        print(json.dumps({**last, "kernel_parity": parity}), flush=True)
        if parity.get("fail") or parity.get("error"):
            return 1
    return 0


def _setup_child_telemetry() -> None:
    """Instrument the measured child: file-backed telemetry, compile
    tracker, and a fast heartbeat (10s default instead of 60: bench
    bodies are minutes long, and the heartbeat is what leaves evidence
    on disk when the run is killed mid-compile)."""
    from huggingface_sagemaker_tensorflow_distributed_tpu import obs

    out = (os.environ.get(obs.ENV_DIR, "").strip()
           or os.path.join(os.getcwd(), "telemetry"))
    obs.configure(out_dir=out)
    if not obs.has_sink():
        return
    obs.compile_tracker()
    hb = obs.heartbeat(interval=obs.heartbeat_env_interval(default=10.0))
    hb.start()
    hb.watch_current_thread()
    import atexit

    atexit.register(obs.shutdown)


def _install_child_budget(args: argparse.Namespace) -> None:
    """SIGALRM/SIGTERM → partial-result JSON + telemetry flush + exit 1.
    The alarm leads the supervisor's kill by design; if the process is
    wedged in native code where Python signals can't run, the heartbeat
    thread has been flushing trace.json all along and the supervisor
    has already forwarded whatever the child printed."""
    budget = os.environ.get("_BENCH_CHILD_BUDGET", "").strip()
    try:
        budget_s = float(budget) if budget else args.budget_seconds
    except ValueError:
        budget_s = args.budget_seconds
    if budget_s is None:
        return
    import signal

    metrics = _mode_metrics(args)

    def _bail(signum, frame):
        try:
            from huggingface_sagemaker_tensorflow_distributed_tpu import obs
            obs.flush()
        except Exception:  # noqa: BLE001 — partial emission must not die
            pass
        # leading newline: the alarm may land mid-print of a metric
        # line; starting fresh keeps the final stdout line parseable
        # (the whole point of the partial-result contract)
        sys.stdout.write("\n")
        emit_error(metrics, "budget_exceeded",
                   {"budget_s": budget_s, "signal": int(signum),
                    "partial": True})
        sys.stdout.flush()
        os._exit(1)

    signal.signal(signal.SIGTERM, _bail)
    if hasattr(signal, "SIGALRM"):
        signal.signal(signal.SIGALRM, _bail)
        signal.alarm(max(int(budget_s) - 5, 1))


def _check_divergence_exit() -> None:
    """NaN-loss gate (CI contract): a measured body whose training loss
    went non-finite exits ``ANOMALY_RC`` AFTER its metric lines are on
    stdout — silent divergence must not look like a healthy bench."""
    from huggingface_sagemaker_tensorflow_distributed_tpu import obs

    counts = obs.anomaly_counts()
    if counts.get("nan_loss") or counts.get("nan_grad"):
        print(f"[bench] divergence anomalies detected: {counts} — "
              "exiting nonzero", file=sys.stderr)
        try:
            obs.flush()
        except Exception:  # noqa: BLE001
            pass
        sys.stdout.flush()
        sys.exit(ANOMALY_RC)


def _run_child(args: argparse.Namespace) -> None:
    from huggingface_sagemaker_tensorflow_distributed_tpu.parallel import (
        enable_compilation_cache,
        require_accelerator,
    )

    metrics = _mode_metrics(args)
    try:
        device = require_accelerator()
    except RuntimeError as e:
        # no TPU and the CPU not asked for by name, or a listed
        # platform that cannot initialize
        emit_error(metrics, "backend_unreachable", {"message": str(e)[:500]})
        sys.exit(1)
    emit_provisional(metrics, "device", device=device)
    enable_compilation_cache()
    _setup_child_telemetry()
    _install_child_budget(args)
    if args.mesh:
        from benchmarks.mesh_bench import bench_mesh
        bench_mesh()
    elif args.buckets:
        from benchmarks.bucket_bench import bench_buckets
        bench_buckets()
    elif args.generate:
        from benchmarks.generate_bench import bench_generate
        bench_generate()
    elif args.causal_lm:
        from benchmarks.causal_lm_bench import bench_causal_lm
        bench_causal_lm()
    elif args.mlm:
        from benchmarks.mlm_bench import bench_mlm
        bench_mlm()
    elif args.banded:
        from benchmarks.banded_bench import bench_banded
        bench_banded()
    elif getattr(args, "data", False):
        from benchmarks.data_bench import bench_data
        bench_data()
    elif getattr(args, "serve", False):
        from benchmarks.serve_bench import bench_serve
        bench_serve()
    elif args.llama_train:
        from benchmarks.llama_train_bench import bench_llama_train
        bench_llama_train()
    elif args.mixtral_train:
        from benchmarks.mixtral_train_bench import bench_mixtral_train
        bench_mixtral_train()
    elif args.lora:
        bench_lora()
    elif args.model == "bert-large":
        bench_bert_large()
    else:
        bench_headline(per_chip_batch=args.batch,
                       opt_state_bf16=args.opt_state_bf16,
                       remat_policy=args.remat_policy)
    _check_divergence_exit()


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--model", choices=["bert-base", "bert-large"],
                        default=None)
    parser.add_argument("--buckets", action="store_true")
    parser.add_argument("--mesh", action="store_true")
    parser.add_argument("--generate", action="store_true")
    parser.add_argument("--causal-lm", action="store_true", dest="causal_lm")
    parser.add_argument("--mlm", action="store_true")
    parser.add_argument("--lora", action="store_true",
                        help="BERT-large + LoRA r=8: adapter-only "
                             "optimizer state buys batch 32 on one chip")
    parser.add_argument("--banded", action="store_true",
                        help="banded-flash microbench (sliding window vs "
                             "full causal at seq 8192)")
    parser.add_argument("--data", action="store_true",
                        help="input-pipeline microbench: prefetch-depth "
                             "autotune consumer-wait reduction + pad-waste "
                             "bucketing-vs-packing (CPU-friendly)")
    parser.add_argument("--serve", action="store_true",
                        help="continuous-batching serving bench: mixed-"
                             "length request trace through serve/engine "
                             "(paged KV + iteration-level scheduling) vs "
                             "static-batch generate_causal (TTFT "
                             "p50/p99, aggregate tokens/sec, KV-pool "
                             "utilization, compile flatness) + the "
                             "bucketed-gather decode speedup on a "
                             "short-context trace + the speculative "
                             "draft/verify decode speedup on a high-"
                             "acceptance trace + the tensor-parallel "
                             "shard-capacity line (TP=2 vs TP=1 on "
                             "the same per-device KV byte budget) + "
                             "the multi-replica router scale-out line "
                             "(2 engine replicas vs 1: placement-"
                             "policy token identity, 2x fleet "
                             "admission depth, affinity-vs-round-"
                             "robin cache hit rate, load imbalance) + "
                             "the open-loop goodput line (Poisson "
                             "arrival schedule on a virtual clock: "
                             "SLO attainment at underload/overload "
                             "rates, queue-dominant miss attribution, "
                             "wall-clock capacity knee reported)")
    parser.add_argument("--lint", action="store_true",
                        help="graftlint static-analysis stage: emit a "
                             "lint_findings count line (0 = clean; "
                             "count metric, worse direction UP, "
                             "zero-baseline regression rule shared "
                             "with compiles/anomalies). Runs "
                             "in-process and jax-less")
    parser.add_argument("--llama-train", action="store_true",
                        dest="llama_train",
                        help="TinyLlama-1.1B training throughput "
                             "(bf16 Adam + remat dots + fused CE)")
    parser.add_argument("--mixtral-train", action="store_true",
                        dest="mixtral_train",
                        help="sparse-MoE (Mixtral-style, 8 experts "
                             "alternating) training throughput, routed-"
                             "FLOPs MFU convention")
    parser.add_argument("--batch", type=int, default=None,
                        help="per-chip batch override (headline mode)")
    parser.add_argument("--opt-state-bf16", action="store_true",
                        dest="opt_state_bf16",
                        help="bf16 Adam m/v storage (halved optimizer HBM; "
                             "headline mode)")
    parser.add_argument("--remat-policy", dest="remat_policy", default=None,
                        choices=["full", "dots", "dots_no_batch"],
                        help="enable encoder remat with this checkpoint "
                             "policy (headline mode; default: remat off)")
    parser.add_argument("--budget-seconds", dest="budget_seconds",
                        type=float, default=_default_budget(),
                        help="overall deadline for this invocation: the "
                             "measured child and the parity subset "
                             "share it, and on expiry the run degrades "
                             "to partial-result JSON (rc 1) instead of "
                             "an empty tail (default: "
                             "BENCH_BUDGET_SECONDS env or unbounded)")
    parser.add_argument("--_child", action="store_true",
                        help=argparse.SUPPRESS)  # internal: run measured body
    args = parser.parse_args()
    picked = [n for n, on in [("--model", args.model is not None),
                              ("--buckets", args.buckets),
                              ("--mesh", args.mesh),
                              ("--generate", args.generate),
                              ("--causal-lm", args.causal_lm),
                              ("--mlm", args.mlm),
                              ("--lora", args.lora),
                              ("--banded", args.banded),
                              ("--data", args.data),
                              ("--serve", args.serve),
                              ("--lint", args.lint),
                              ("--llama-train", args.llama_train),
                              ("--mixtral-train", args.mixtral_train)] if on]
    if len(picked) > 1:
        parser.error(f"pick one mode, got {' and '.join(picked)}")
    if (args.batch is not None or args.opt_state_bf16
            or args.remat_policy) and picked:
        # headline-only knobs: other modes hardcode their configuration,
        # so dropping these silently would mislabel the measurement
        parser.error("--batch/--opt-state-bf16/--remat-policy apply to "
                     f"the headline mode only, not {picked[0]}")

    if args.lint:
        # no supervised child: the stage is stdlib-only and sub-second,
        # and the child/budget machinery exists for jax workloads
        bench_lint()
    elif getattr(args, "_child"):
        _run_child(args)
    else:
        sys.exit(supervise(args))


if __name__ == "__main__":
    main()
