"""The expanded latent attention of ONE prefill dispatch, timed alone on
the chip: the fused kernel (``ops/pallas_latent_attention.py``) against
the XLA key-block loop (``models/deepseek_v2.py::attend_expanded``), by
hand (PR 32).

    python3 -m chipbench.tools.latent_prefill_microbench \\
        --rows 4,1 --buckets 2048,8192 --starts 1024,4096,7680 \\
        --mixed 512:2048:4096:7680 --out <file.jsonl>

One JSON line a (rows, bucket, start, form): milliseconds a layer-call,
the FLOPs the call NEEDS (per row, head and key block up to the row's
last query: the block's ``k_nope | v`` expanded from its latent rows,
the scores against both parts of the key, the values; blocks on the
diagonal counted whole, as both forms compute them) and their time at
the device's bf16 peak (``chipbench/peaks.json``) as a share of the time
measured, and the largest difference between the two forms' outputs.
``--starts`` gives every row of a dispatch the same start (a start that
does not fit a bucket with its chunk is left out there); ``--mixed
a:b:c:d`` is one dispatch whose rows differ, where the kernel skips a row
at a time and the XLA loop runs every block that any row sees. Shapes
default to ``deepseek-v2-ep4-doc-sat``'s: 128 heads of 128 + 64 rotary,
values of 128, rank 512, rows of 640, chunks of 512, bf16.

The time is the device's: ``--iters`` calls chained inside ONE jitted
program (each call's query depends on the last call's output), the
program timed on the host's clock around ``block_until_ready``, the best
of ``--repeats``. Off a TPU it runs the kernel in interpret mode at
whatever size it is given and says so: its times are then no device's."""

from __future__ import annotations

import argparse
import functools
import json
import sys
import time

import numpy as np


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", default="4,1")
    ap.add_argument("--buckets", default="2048,8192")
    ap.add_argument("--starts", default="1024,4096,7680")
    ap.add_argument("--mixed", default=None,
                    help="one more dispatch, a start a row: a:b:c:d")
    ap.add_argument("--heads", type=int, default=128)
    ap.add_argument("--chunk", type=int, default=512)
    ap.add_argument("--rank", type=int, default=512)
    ap.add_argument("--nope", type=int, default=128)
    ap.add_argument("--rope", type=int, default=64)
    ap.add_argument("--v-dim", type=int, default=128)
    ap.add_argument("--block", type=int, default=None,
                    help="the kernel's query and key block (default: the "
                         "model's KEY_BLOCK, which the XLA loop keeps)")
    ap.add_argument("--q-rows", default=None,
                    help="sweep the queries a pass of a kernel step "
                         "attends (the kernel's module constant _Q_ROWS): "
                         "a list. Default: as it ships")
    ap.add_argument("--dtype", default="bfloat16",
                    choices=("bfloat16", "float32"))
    ap.add_argument("--forms", default="kernel,xla_loop")
    ap.add_argument("--iters", type=int, default=6)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from jax import lax

    from chipbench import arith
    from huggingface_sagemaker_tensorflow_distributed_tpu.models import (
        deepseek_v2 as D,
    )
    from huggingface_sagemaker_tensorflow_distributed_tpu.ops import (
        pallas_latent_attention as kernel,
    )

    dev = jax.devices()[0]
    try:
        peak = arith.peaks(dev.device_kind)
    except LookupError:
        peak = None                     # the CPU rehearsal: no device's time
    H, C, rank, nope, rot, vd = (args.heads, args.chunk, args.rank,
                                 args.nope, args.rope, args.v_dim)
    row = -(-(rank + rot) // 128) * 128
    block = args.block or D.KEY_BLOCK
    scale = (nope + rot) ** -0.5
    dtype = jnp.dtype(args.dtype)
    rng = np.random.RandomState(args.seed)
    w = jnp.asarray(rng.randn(rank, H, nope + vd) * 0.05, dtype)
    # (form, queries a pass): the kernel once a --q-rows value
    forms = [(f, int(r) if r else None) for f in args.forms.split(",")
             for r in ((args.q_rows or "").split(",") if f == "kernel"
                       else [""])]

    dispatches = [(G, [s] * G) for G in map(int, args.rows.split(","))
                  for s in map(int, args.starts.split(","))]
    if args.mixed:
        mixed = [int(s) for s in args.mixed.split(":")]
        dispatches.append((len(mixed), mixed))

    for width in map(int, args.buckets.split(",")):
        for G, starts in dispatches:
            if max(starts) + C > width:
                continue
            q_nope = jnp.asarray(rng.randn(G, C, H, nope), dtype)
            q_pe = jnp.asarray(rng.randn(G, C, H, rot), dtype)
            latent = jnp.asarray(np.pad(
                rng.randn(G, width, rank + rot),
                [(0, 0), (0, 0), (0, row - rank - rot)]), dtype)
            start = jnp.asarray(starts, jnp.int32)
            valid = jnp.arange(width)[None, :] < start[:, None] + C
            blocks = sum(-(-(s + C) // block) for s in starts)
            flops = blocks * H * 2 * block * (
                rank * (nope + vd) + C * (nope + rot + vd))

            # everything an argument: a closed-over array is a constant
            # of the compiled program, 245 MB of it at these shapes
            operands = (q_pe, latent, w, start, valid)

            def attend(form, q_nope, q_pe, latent, w, start, valid):
                if form == "kernel":
                    return kernel.latent_prefill_attention(
                        q_nope, q_pe, latent, w, start, valid, rank=rank,
                        scale=scale, block=block)
                return D.attend_expanded(
                    q_nope, q_pe, latent,
                    D.mask_bias(start, C, valid, width), w, rank=rank,
                    scale=scale)

            def chained(form):
                def run(q_nope, *operands):
                    def body(_, q):
                        out = attend(form, q, *operands)
                        return (q + out[..., :nope] * 1e-3).astype(q.dtype)
                    return lax.fori_loop(0, args.iters, body, q_nope)
                return jax.jit(run)

            outs = []
            for form, q_rows in forms:
                if q_rows:
                    # read when a call is traced: every form is traced here
                    kernel._Q_ROWS = q_rows
                outs.append(np.asarray(
                    jax.jit(functools.partial(attend, form))(
                        q_nope, *operands), np.float32))
                run = chained(form)
                jax.block_until_ready(run(q_nope, *operands))  # compiles
                best = float("inf")
                for _ in range(args.repeats):
                    t0 = time.perf_counter()
                    jax.block_until_ready(run(q_nope, *operands))
                    best = min(best, time.perf_counter() - t0)
                ms = best / args.iters * 1e3
                line = {
                    "form": form, "rows": G, "bucket": width,
                    "starts": starts if len(set(starts)) > 1 else starts[0],
                    "heads": H, "chunk": C, "block": block,
                    "q_rows": (min(block, kernel._Q_ROWS)
                               if form == "kernel" else None),
                    "dtype": args.dtype, "ms_per_layer_call": ms,
                    "flops_needed": flops, "key_blocks_needed": blocks,
                    "platform": dev.platform, "device_kind": dev.device_kind,
                    "max_abs_diff_vs_first_form": float(
                        np.abs(outs[-1] - outs[0]).max()),
                }
                if peak is not None:
                    floor_ms = flops / (peak["bf16_tflops"] * 1e12) * 1e3
                    line["mxu_peak_ms"] = floor_ms
                    line["mxu_peak_share"] = 100.0 * floor_ms / ms
                else:
                    line["note"] = ("no peak for this device: the time is "
                                    "no accelerator's")
                print(json.dumps(line), flush=True)
                if args.out:
                    with open(args.out, "a") as f:
                        f.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
