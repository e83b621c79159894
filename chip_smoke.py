"""The quickest proof that the system still starts on the chip.

Drives the main path once through the entry points a user calls, at the
published widths of the two models the repository is built around, with
random weights from a seed, and checks what comes out by the
repository's own means:

  fine-tune   ``scripts/train.py``: BERT-base (12 layers, hidden 768, 12
              heads, FFN 3,072, vocab 30,522), seq 512, bf16, per-chip
              batch 32, ten optimizer steps, a checkpoint (replicas
              compared first), eval, export.
  causal-lm   ``scripts/train.py --task causal-lm --fused_vocab_ce true``:
              GPT-2 124M (12 layers, hidden 768, 12 heads, vocab 50,257),
              seq 512, four steps, checkpoint, eval, export.
  serve       ``scripts/serve.py --model_dir <that export>
              --max_model_len 1024``: twelve requests, prompts of 64-512
              tokens, 32-64 new tokens, defaults otherwise; then once
              more with ``--kernel pallas`` and, on a host with several
              chips, with ``--tp <chips>``. Afterwards greedy output of
              two requests, from every one of those runs, is compared
              with ``generate_causal`` on the same weights.

Every leg is its own process and the only one on the chip while it runs;
this parent never touches JAX. Training uses every chip it finds
(``--dp -1``), so the same file is the four-chip run. Per leg it prints
the device as JAX reported it, wall time split into compile and steady,
and per-device peak memory; it asserts from the lowered text of the
compiled train step that the Pallas flash and fused vocab-CE kernels are
Mosaic custom calls there (an interpret-mode kernel lowers to none). The
compile cache is ``JAX_COMPILATION_CACHE_DIR`` if set, else
``.jax_cache/`` beside this file, so a second run reports the hit.

    python chip_smoke.py                  # on the chip: fails without a TPU
    python chip_smoke.py --rehearse-cpu   # here: tiny widths, JAX_PLATFORMS=cpu

Exit code 0 and a last stdout line
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``
only if every leg passed; otherwise non-zero and no such line.
"""

from __future__ import annotations

import argparse
import ctypes
import glob
import json
import math
import os
import random
import shutil
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
PACKAGE = "huggingface_sagemaker_tensorflow_distributed_tpu"
OUT = os.path.join(ROOT, "smoke_out")
NATIVE_LIB = os.path.join(ROOT, "native", "libhstd_native.so")
LEG_TIMEOUT_S = 900

# published widths (BERT: Devlin et al. 2018, bert-base-uncased
# config.json; GPT-2: Radford et al. 2019, gpt2 config.json)
BERT_BASE = {"model_type": "bert", "vocab_size": 30522, "hidden_size": 768,
             "num_hidden_layers": 12, "num_attention_heads": 12,
             "intermediate_size": 3072, "max_position_embeddings": 512,
             "type_vocab_size": 2, "hidden_act": "gelu",
             "hidden_dropout_prob": 0.1, "attention_probs_dropout_prob": 0.1,
             "layer_norm_eps": 1e-12}
# attn_pdrop is 0.1 as published; 0.0 here because dropout on the
# attention probabilities has no hook in the fused kernel — with it on,
# models/gpt2.py trains through unfused O(S^2) attention (and warns)
GPT2_124M = {"model_type": "gpt2", "vocab_size": 50257, "n_positions": 1024,
             "n_embd": 768, "n_layer": 12, "n_head": 12,
             "activation_function": "gelu_new", "resid_pdrop": 0.1,
             "embd_pdrop": 0.1, "attn_pdrop": 0.0,
             "layer_norm_epsilon": 1e-5, "bos_token_id": 50256,
             "eos_token_id": 50256}

# sized for one chip or one four-chip host: the global batch is the
# per-chip batch times every chip found
MAX_CHIPS = 4
FULL = dict(bert=BERT_BASE, gpt2=GPT2_124M, seq=512, dtype="bfloat16",
            ft_batch=32, ft_steps=10, lm_batch=8, lm_steps=4,
            max_model_len=1024, prompt_lens=(64, 128, 96, 256, 512, 200,
                                             64, 320, 448, 80, 160, 384),
            new_tokens=(32, 48, 64), serve_args=())
# the same legs at sizes the CPU finishes in seconds (control flow and
# artefact checks only; flash runs in interpret mode, fused CE falls
# back, so the kernel assertion is skipped)
TINY = dict(
    bert={**BERT_BASE, "vocab_size": 512, "hidden_size": 32,
          "num_hidden_layers": 2, "num_attention_heads": 2,
          "intermediate_size": 64, "max_position_embeddings": 64},
    gpt2={**GPT2_124M, "vocab_size": 512, "n_positions": 128, "n_embd": 32,
          "n_layer": 2, "n_head": 4, "bos_token_id": 511,
          "eos_token_id": 511},
    seq=32, dtype="float32", ft_batch=2, ft_steps=10, lm_batch=2, lm_steps=4,
    max_model_len=64, prompt_lens=(8, 16, 12, 30, 9, 20), new_tokens=(4, 6),
    serve_args=("--num_slots", "4", "--block_size", "8",
                "--prefill_chunk", "8"))


class LegFailed(Exception):
    pass


class NoAccelerator(LegFailed):
    """A leg's device guard refused the backend: no leg can pass."""


def check(cond, message: str) -> None:
    if not cond:
        raise LegFailed(message)


def cache_dir() -> str:
    """Same rule as ``parallel/distributed.py::compilation_cache_dir``
    (restated: this parent imports nothing that imports jax)."""
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(ROOT, ".jax_cache"))


def child_env(leg_dir: str, rehearse: bool, dump_ir: bool) -> dict:
    env = dict(os.environ)
    env["JAX_COMPILATION_CACHE_DIR"] = cache_dir()
    env["HSTD_TELEMETRY_DIR"] = os.path.join(leg_dir, "telemetry")
    env["PYTHONUNBUFFERED"] = "1"
    if rehearse:
        env["JAX_PLATFORMS"] = "cpu"
    if dump_ir:
        # the lowered StableHLO of every jitted function: the compiled
        # train step's text is the kernel evidence
        env["JAX_DUMP_IR_TO"] = os.path.join(leg_dir, "ir")
        env["JAX_DUMP_IR_MODES"] = "stablehlo"
    return env


def run_process(argv: list[str], leg_dir: str, env: dict) -> tuple[str, float]:
    """Run one child to completion; returns (stdout, wall seconds).
    stderr goes to ``<leg_dir>/log.txt``; a non-zero exit or a timeout
    fails the leg with the log's tail."""
    os.makedirs(leg_dir, exist_ok=True)
    log_path = os.path.join(leg_dir, "log.txt")
    t0 = time.monotonic()
    with open(log_path, "w") as log:
        try:
            proc = subprocess.run(argv, cwd=ROOT, env=env, text=True,
                                  stdout=subprocess.PIPE, stderr=log,
                                  timeout=LEG_TIMEOUT_S)
        except subprocess.TimeoutExpired:
            raise LegFailed(f"timed out after {LEG_TIMEOUT_S}s "
                            f"(log: {log_path})") from None
    wall = time.monotonic() - t0
    with open(os.path.join(leg_dir, "stdout.txt"), "w") as f:
        f.write(proc.stdout)
    if proc.returncode != 0:
        with open(log_path) as f:
            tail = f.read()[-3000:]
        kind = NoAccelerator if "NoAcceleratorError" in tail else LegFailed
        raise kind(f"exit code {proc.returncode}; end of {log_path}:\n"
                   f"{tail}")
    return proc.stdout, wall


def read_events(leg_dir: str) -> list[dict]:
    path = os.path.join(leg_dir, "telemetry", "events.jsonl")
    check(os.path.exists(path), f"no telemetry at {path}")
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def spans(events: list[dict], name: str) -> list[float]:
    return [e["dur"] for e in events
            if e["type"] == "span" and e["name"] == name]


def compile_seconds(events: list[dict]) -> float:
    """Lowering + backend compile, summed (what the persistent cache
    saves; a cache hit is a near-zero backend compile)."""
    compiles = [e for e in events if e["type"] == "compile"]
    return round(compiles[-1]["cum"], 3) if compiles else 0.0


def read_results(path: str) -> dict:
    check(os.path.exists(path), f"missing {path}")
    out = {}
    with open(path) as f:
        for line in f:
            if " = " in line:
                key, value = line.strip().split(" = ", 1)
                out[key] = value
    return out


def device_of(facts: dict, rehearse: bool) -> dict:
    device = {"platform": facts["platform"], "kind": facts["device_kind"],
              "count": int(facts["device_count"])}
    want = "cpu" if rehearse else "tpu"
    check(device["platform"] == want,
          f"leg ran on platform {device['platform']!r}, expected {want!r}")
    return device


def mosaic_kernels(leg_dir: str, step_name: str) -> set[str]:
    """Kernel names of the Mosaic custom calls in the lowered text of
    the jitted function ``step_name``."""
    names: set[str] = set()
    for path in glob.glob(os.path.join(leg_dir, "ir", f"*{step_name}*")):
        with open(path, errors="replace") as f:
            for line in f:
                if "tpu_custom_call" not in line:
                    continue
                marker = 'kernel_name = "'
                at = line.find(marker)
                check(at >= 0, f"Mosaic call without a kernel name in {path}")
                start = at + len(marker)
                names.add(line[start:line.index('"', start)])
    return names


def train_leg(name: str, sizes: dict, rehearse: bool, config: dict,
              batch: int, steps: int, extra: list[str],
              kernels: set[str]) -> dict:
    leg_dir = os.path.join(OUT, name)
    cfg_dir = os.path.join(leg_dir, "config")
    os.makedirs(cfg_dir, exist_ok=True)
    with open(os.path.join(cfg_dir, "config.json"), "w") as f:
        json.dump(config, f)
    out_dir, model_dir, ckpt_dir = (os.path.join(leg_dir, d)
                                    for d in ("output", "model", "ckpt"))
    argv = [sys.executable, os.path.join("scripts", "train.py"),
            "--model_name_or_path", cfg_dir, "--dataset", "synthetic",
            "--from_scratch", "true", "--max_seq_length", str(sizes["seq"]),
            "--dtype", sizes["dtype"], "--train_batch_size", str(batch),
            "--eval_batch_size", str(batch), "--epochs", "1",
            "--steps_per_epoch", str(steps),
            "--max_train_samples", str(batch * steps * MAX_CHIPS),
            "--max_eval_samples", str(batch * 2 * MAX_CHIPS),
            "--dp", "-1", "--log_every_steps", "1", "--seed", "0",
            "--output_data_dir", out_dir, "--model_dir", model_dir,
            "--checkpoint_dir", ckpt_dir, *extra]
    if rehearse:
        # interpret-mode flash: the CPU rehearsal still walks the
        # kernel's code path, shard_map included
        argv += ["--attention_impl", "flash"]
    _, wall = run_process(argv, leg_dir, child_env(leg_dir, rehearse, True))

    train = read_results(os.path.join(out_dir, "train_results.txt"))
    evals = read_results(os.path.join(out_dir, "eval_results.txt"))
    for artefact in ("model.safetensors", "config.json"):
        check(os.path.exists(os.path.join(model_dir, artefact)),
              f"export left no {artefact}")
    device = device_of(train, rehearse)
    events = read_events(leg_dir)
    losses = [e["value"] for e in events
              if e["type"] == "metric" and e["name"] == "train/loss"]
    check(len(losses) == steps, f"{len(losses)} step losses, want {steps}")
    check(all(v is not None and math.isfinite(v) for v in losses),
          f"non-finite training loss: {losses}")
    check(all(math.isfinite(float(v))
              for v in train["loss"].strip("[]").split(",")),
          f"non-finite epoch loss {train['loss']}")
    check(math.isfinite(float(evals["eval_loss"])),
          f"non-finite eval loss {evals['eval_loss']}")
    # the epoch-end save: replicas compared on the devices first
    # (Trainer.check_replica_divergence), then the state written
    divergence = [e["value"] for e in events if e["type"] == "metric"
                  and e["name"] == "train/replica_divergence"]
    check(divergence and all(v == 0.0 for v in divergence),
          f"parameter replicas diverge across devices: {divergence}")
    check(os.path.isdir(ckpt_dir) and os.listdir(ckpt_dir),
          f"no checkpoint under {ckpt_dir}")
    found = mosaic_kernels(leg_dir, "_train_step_impl")
    if not rehearse:
        check(kernels <= found, f"compiled train step lacks Mosaic kernels "
              f"{sorted(kernels - found)} (found {sorted(found)})")
    # the first step's dispatch traces, lowers and compiles; the wait
    # after it is the step itself plus whatever compile remained
    first = spans(events, "train/step_dispatch")[0] + sum(
        spans(events, "xla/compile_wait"))
    return {"leg": name, "ok": True, **device,
            "jax_version": train["jax_version"], "wall_s": round(wall, 1),
            "compile_s": compile_seconds(events),
            "first_step_s": round(first, 2),
            "steady_s": round(float(train["train_runtime"]) - first, 2),
            "steps": steps, "global_batch": batch * device["count"],
            "loss_first_last": [round(losses[0], 4), round(losses[-1], 4)],
            "eval_loss": round(float(evals["eval_loss"]), 4),
            "replica_divergence": max(divergence),
            "mosaic_kernels": sorted(found),
            "peak_bytes_in_use": json.loads(
                train["peak_bytes_in_use"].replace("None", "null")),
            "model_dir": model_dir}


def write_requests(path: str, sizes: dict, vocab: int) -> list[dict]:
    rng = random.Random(0)
    rows = []
    for i, n in enumerate(sizes["prompt_lens"]):
        rows.append({
            # ids below the EOS id (the vocabulary's last)
            "prompt_ids": [rng.randrange(1, vocab - 1) for _ in range(n)],
            "max_new_tokens": sizes["new_tokens"][i % len(sizes["new_tokens"])]})
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        for row in rows:
            f.write(json.dumps(row) + "\n")
    return rows


def serve_leg(name: str, sizes: dict, rehearse: bool, model_dir: str,
              eos: int, extra: list[str]) -> dict:
    leg_dir = os.path.join(OUT, name)
    requests_path = os.path.join(leg_dir, "requests.jsonl")
    requests = write_requests(requests_path, sizes, vocab=eos + 1)
    argv = [sys.executable, os.path.join("scripts", "serve.py"),
            "--model_dir", model_dir, "--input_file", requests_path,
            "--max_model_len", str(sizes["max_model_len"]),
            *sizes["serve_args"], *extra]
    stdout, wall = run_process(argv, leg_dir,
                               child_env(leg_dir, rehearse, False))
    records = [json.loads(ln) for ln in stdout.splitlines()
               if ln.startswith("{")]
    rows = {r["request"]: r for r in records if "request" in r}
    summary = next((r for r in records if r.get("summary")), None)
    check(summary is not None, "serve printed no summary line")
    device = device_of(summary, rehearse)
    check(len(rows) == len(requests),
          f"{len(rows)} of {len(requests)} requests finished")
    for rid, req in enumerate(requests):
        out = rows[rid]["output_ids"]
        check(len(out) == req["max_new_tokens"] or (out and out[-1] == eos),
              f"request {rid} stopped after {len(out)} of "
              f"{req['max_new_tokens']} tokens without EOS")
    check(summary["compiles_after_warmup"] == 0,
          f"{summary['compiles_after_warmup']} compile(s) after warm-up")

    events = read_events(leg_dir)
    return {"leg": name, "ok": True, **device,
            "jax_version": summary["jax_version"], "wall_s": round(wall, 1),
            "compile_s": compile_seconds(events),
            "warmup_s": round(sum(spans(events, "serve/warmup")), 2),
            "steady_s": round(sum(spans(events, "serve/run")), 2),
            "requests": len(rows), "tokens": summary["tokens"],
            "kernel": summary["kernel"], "tp": summary["tp"],
            "compiles_after_warmup": summary["compiles_after_warmup"],
            "kv_pool_bytes_per_device": summary["kv_pool_bytes_per_device"],
            "param_bytes_per_device": summary["param_bytes_per_device"],
            "peak_bytes_in_use": summary["peak_bytes_in_use"],
            "outputs": {rid: rows[rid]["output_ids"] for rid in rows},
            "requests_path": requests_path,
            "rows_path": os.path.join(leg_dir, "stdout.txt")}


def oracle_leg(name: str, sizes: dict, rehearse: bool, model_dir: str,
               served: list[dict]) -> dict:
    """The engine's exactness oracle, in a process of its own strictly
    after every server has exited: one reference, every serve leg's
    rows compared with it."""
    leg_dir = os.path.join(OUT, name)
    stdout, wall = run_process(
        [sys.executable, os.path.abspath(__file__), "--oracle", model_dir,
         served[0]["requests_path"], *(s["rows_path"] for s in served)],
        leg_dir, child_env(leg_dir, rehearse, False))
    report = json.loads(stdout.strip().splitlines()[-1])
    check(report["ok"], f"engine disagrees with generate_causal: {report}")
    return {"leg": name, "ok": True, **device_of(report, rehearse),
            "wall_s": round(wall, 1), "against": [s["leg"] for s in served],
            **{k: report[k] for k in ("compared", "exact", "ties")}}


def run_oracle(model_dir: str, requests_path: str,
               rows_paths: list[str]) -> int:
    """Child process (holds the chip alone): greedy output of the first
    two requests, in every serve leg's rows, must equal
    ``generate_causal`` on the same weights. Where they part, the step
    is accepted only as a rounding tie: the engine's token must score
    within 1e-3 of the reference's under a teacher-forced forward pass
    (random weights leave near-equal top logits, and the two paths tile
    their matmuls differently)."""
    sys.path.insert(0, ROOT)
    import jax.numpy as jnp
    import numpy as np

    from huggingface_sagemaker_tensorflow_distributed_tpu.models import auto
    from huggingface_sagemaker_tensorflow_distributed_tpu.models.generate import (
        generate_causal,
    )
    from huggingface_sagemaker_tensorflow_distributed_tpu.parallel import (
        enable_compilation_cache,
        require_accelerator,
    )

    device = require_accelerator()
    enable_compilation_cache()
    model, params, _, config = auto.from_pretrained(model_dir,
                                                    task="causal-lm")
    with open(requests_path) as f:
        requests = [json.loads(line) for line in f][:2]
    report = {**device, "ok": True, "compared": 0, "exact": 0, "ties": []}
    refs = []
    for req in requests:
        ref = [int(t) for t in np.asarray(generate_causal(
            model, params, np.asarray(req["prompt_ids"], np.int32)[None],
            max_new_tokens=req["max_new_tokens"]))[0]]
        if config.eos_token_id in ref:
            ref = ref[:ref.index(config.eos_token_id) + 1]
        refs.append(ref)
    for rows_path in rows_paths:
        with open(rows_path) as f:
            rows = [json.loads(ln) for ln in f
                    if ln.startswith('{"request"')]
        rows = {r["request"]: r["output_ids"] for r in rows}
        for rid, (req, ref) in enumerate(zip(requests, refs)):
            got = rows[rid]
            report["compared"] += 1
            if got == ref:
                report["exact"] += 1
                continue
            at = next((i for i, (a, b) in enumerate(zip(got, ref))
                       if a != b), min(len(got), len(ref)))
            gap = None
            if at < min(len(got), len(ref)):
                ids = np.asarray(req["prompt_ids"] + got[:at], np.int32)
                logits = np.asarray(model.apply(
                    {"params": params}, jnp.asarray(ids)[None])[0, -1])
                gap = float(logits[ref[at]] - logits[got[at]])
            tie = gap is not None and abs(gap) <= 1e-3
            report["ties"].append({"rows": rows_path, "request": rid,
                                   "step": at, "gap": gap})
            report["ok"] = report["ok"] and tie
    print(json.dumps(report))
    return 0 if report["ok"] else 1


def native_status(prebuilt: bool) -> dict:
    """Whether ``native/libhstd_native.so`` was there before the run and
    loads after it (``data/native.py`` builds it with g++ on first use
    and falls back to pure Python with a warning if that fails)."""
    loaded = False
    if os.path.exists(NATIVE_LIB):
        try:
            ctypes.CDLL(NATIVE_LIB)
            loaded = True
        except OSError:
            pass
    return {"native_prebuilt": prebuilt, "native_loaded": loaded}


def run_smoke(rehearse: bool) -> int:
    from huggingface_sagemaker_tensorflow_distributed_tpu.obs.flops import (
        PEAK_TFLOPS_TABLE,      # stdlib-only module: no jax in this parent
    )

    sizes = TINY if rehearse else FULL
    first = os.environ.get("JAX_PLATFORMS", "").split(",")[0].strip().lower()
    if not rehearse and first == "cpu":
        print("chip_smoke: JAX_PLATFORMS asks for the CPU; this is the chip "
              "smoke (python chip_smoke.py --rehearse-cpu rehearses it at "
              "tiny widths)", file=sys.stderr)
        return 2
    prebuilt = os.path.exists(NATIVE_LIB)
    print(f"chip_smoke: output {OUT}, compile cache {cache_dir()}",
          file=sys.stderr)
    # this run's artefacts only: telemetry files are appended to
    shutil.rmtree(OUT, ignore_errors=True)
    eos = sizes["gpt2"]["eos_token_id"]
    flash = {"flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"}
    vocab_ce = {"vocab_ce_fwd", "vocab_ce_bwd_dh", "vocab_ce_bwd_dw"}
    reports: list[dict] = []
    failed: list[str] = []

    def leg(name, fn, *args):
        try:
            report = fn(name, sizes, rehearse, *args)
        except NoAccelerator as e:
            print(f"chip_smoke: leg {name}: {e}", file=sys.stderr)
            sys.exit(3)
        except LegFailed as e:
            failed.append(name)
            print(f"chip_smoke: leg {name} FAILED: {e}", file=sys.stderr)
            print(json.dumps({"leg": name, "ok": False}), flush=True)
            return None
        reports.append(report)
        print(json.dumps({k: v for k, v in report.items() if k not in (
            "outputs", "model_dir", "requests_path", "rows_path")}),
            flush=True)
        return report

    leg("fine-tune", train_leg, sizes["bert"], sizes["ft_batch"],
        sizes["ft_steps"], ["--task", "seq-cls"], flash)
    print(json.dumps(native_status(prebuilt)), flush=True)
    lm = leg("causal-lm", train_leg, sizes["gpt2"], sizes["lm_batch"],
             sizes["lm_steps"],
             ["--task", "causal-lm", "--fused_vocab_ce", "true"],
             flash | vocab_ce)
    if lm is None:
        failed.append("serve (needs the causal-lm export)")
    else:
        served = [leg("serve", serve_leg, lm["model_dir"], eos, []),
                  leg("serve-pallas", serve_leg, lm["model_dir"], eos,
                      ["--kernel", "pallas"])]
        chips = lm["count"]
        if chips > 1 and sizes["gpt2"]["n_head"] % chips == 0:
            served.append(leg("serve-tp", serve_leg, lm["model_dir"], eos,
                              ["--tp", str(chips)]))
        served = [s for s in served if s is not None]
        if served:
            leg("oracle", oracle_leg, lm["model_dir"], served)
        base, tp = ([s for s in served if s["leg"] == name]
                    for name in ("serve", "serve-tp"))
        if base and tp:
            # params and pools really split `chips` ways
            base, tp = base[0], tp[0]
            split_ok = (tp["tp"] == chips
                        and tp["kv_pool_bytes_per_device"] * chips
                        == base["kv_pool_bytes_per_device"]
                        and tp["param_bytes_per_device"]
                        < base["param_bytes_per_device"])
            print(json.dumps({
                "leg": "serve-tp", "split_ok": split_ok,
                "tokens_equal_to_tp1": sum(
                    tp["outputs"][r] == base["outputs"][r]
                    for r in base["outputs"]),
                "of": len(base["outputs"])}), flush=True)
            if not split_ok:
                failed.append("serve-tp (params/pools not split)")

    if failed:
        print(f"chip_smoke: FAILED legs: {failed}", file=sys.stderr)
        return 1
    devices = [{k: r[k] for k in ("platform", "kind", "count")}
               for r in reports]
    device = devices[0]
    if any(d != device for d in devices):
        print(f"chip_smoke: legs disagree on the device: {devices}",
              file=sys.stderr)
        return 1
    if not rehearse and not any(marker in device["kind"].lower()
                                for marker, _ in PEAK_TFLOPS_TABLE):
        print(f"chip_smoke: device_kind {device['kind']!r} is not in the "
              "peaks table of obs/flops.py", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device,
                      **({"rehearsal": True} if rehearse else {})}))
    return 0


def main(argv: list[str]) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--rehearse-cpu", action="store_true",
                        help="tiny widths under JAX_PLATFORMS=cpu: control "
                             "flow and artefact checks only")
    parser.add_argument("--oracle", nargs="+", help=argparse.SUPPRESS,
                        metavar="MODEL_DIR REQUESTS ROWS...")
    args = parser.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, PACKAGE)):
        print(f"chip_smoke: {ROOT} holds no checkout of the repository "
              f"(no {PACKAGE}/)", file=sys.stderr)
        return 2
    if args.oracle:
        model_dir, requests_path, *rows_paths = args.oracle
        return run_oracle(model_dir, requests_path, rows_paths)
    return run_smoke(args.rehearse_cpu)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
