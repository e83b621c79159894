"""One decode layer-call of attention over a paged cache, timed alone
on the chip: the fused kernel (``ops/pallas_paged_attention.py``)
against the gather path (``ops/attention.py::paged_attention(impl=
"xla")``), by hand (PR 29).

    python3 -m chipbench.tools.paged_decode_microbench \\
        --slots 16 --kv-heads 2 --group 8 --head-dim 128 --block-size 16 \\
        --buckets 2048,8192 --contexts 64:1536:410 --dtype bfloat16 \\
        --block-keys 256,512,1024 --out <file.jsonl>

One JSON line a (bucket, path, compute block): microseconds a
layer-call, the bytes the call NEEDS (the K and V rows of the resident
tokens, with their scales for int8 pools) and their time at the
device's memory peak (``chipbench/peaks.json``) as a share of the time
measured, and the largest difference between the two paths' outputs.
It is how the head-size rule of ``serve/engine.py::resolve_decode_path``
was decided (``PERF.md`` quotes its lines): run it at a new geometry
before adding a head size there.

The time is the device's: ``--iters`` calls chained inside ONE jitted
program (each call's query depends on the last call's output), the
program timed on the host's clock around ``block_until_ready``, the
best of ``--repeats``. ``--contexts min:max:mean`` spreads the slots'
contexts geometrically between the ends, bent to the mean; ``n`` gives
every slot ``n``. Block tables are a seeded permutation of a pool of
``--pool-blocks`` blocks: pages lie scattered, as a served pool's do.
Off a TPU it runs the kernel in interpret mode at whatever size it is
given and says so: its times are then no device's."""

from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np


def contexts(spec: str, slots: int, width: int) -> np.ndarray:
    """``n`` or ``min:max:mean`` as ``slots`` context lengths <= width."""
    parts = [int(p) for p in spec.split(":")]
    if len(parts) == 1:
        return np.full((slots,), min(parts[0], width), np.int32)
    lo, hi, mean = parts
    hi = min(hi, width)
    u = np.linspace(0.0, 1.0, slots)
    g_lo, g_hi = 0.05, 20.0
    for _ in range(60):                 # bend the spacing to the mean
        g = (g_lo * g_hi) ** 0.5
        if (lo * (hi / lo) ** (u ** g)).mean() > mean:
            g_lo = g
        else:
            g_hi = g
    return np.round(lo * (hi / lo) ** (u ** g)).astype(np.int32)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--slots", type=int, default=16)
    ap.add_argument("--kv-heads", type=int, default=2)
    ap.add_argument("--group", type=int, default=8,
                    help="query heads a KV head")
    ap.add_argument("--head-dim", type=int, default=128)
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--buckets", default="2048,8192")
    ap.add_argument("--contexts", default="64:1536:410")
    ap.add_argument("--dtype", default="bfloat16",
                    choices=("bfloat16", "float32"))
    ap.add_argument("--kv", default="fp", choices=("fp", "int8"))
    ap.add_argument("--window", type=int, default=None)
    ap.add_argument("--block-keys", default=None,
                    help="sweep the kernel's compute block (its module "
                         "constant _BLOCK_KEYS; the byte bound still "
                         "holds): a list of keys. Default: as it ships")
    ap.add_argument("--pool-blocks", type=int, default=7629,
                    help="blocks of one layer's pool (7,629: chat-sat's), "
                         "or as many as the widest bucket's tables need")
    ap.add_argument("--iters", type=int, default=100)
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from jax import lax

    from chipbench import arith
    from huggingface_sagemaker_tensorflow_distributed_tpu.ops.attention import (
        paged_attention,
    )
    from huggingface_sagemaker_tensorflow_distributed_tpu.ops import (
        pallas_paged_attention as kernel,
    )

    dev = jax.devices()[0]
    try:
        peak = arith.peaks(dev.device_kind)
    except LookupError:
        peak = None                     # the CPU rehearsal: no device's time
    S, Hkv, G, D, bs = (args.slots, args.kv_heads, args.group,
                        args.head_dim, args.block_size)
    dtype = jnp.dtype(args.dtype)
    rng = np.random.RandomState(args.seed)
    buckets = [int(b) for b in args.buckets.split(",")]
    N = max(args.pool_blocks, S * max(buckets) // bs + 1)
    q = jnp.asarray(rng.randn(S, Hkv * G, D) * 0.3, dtype)
    kv = [rng.randn(N, bs, Hkv, D).astype(np.float32) * 0.3
          for _ in range(2)]
    scales = {}
    if args.kv == "int8":
        sc = [np.abs(x).max(-1, keepdims=True) / 127.0 + 1e-8 for x in kv]
        pools = [jnp.asarray(np.clip(np.round(x / s), -127, 127), jnp.int8)
                 for x, s in zip(kv, sc)]
        scales = dict(k_scale_pool=jnp.asarray(sc[0], jnp.float32),
                      v_scale_pool=jnp.asarray(sc[1], jnp.float32))
    else:
        pools = [jnp.asarray(x, dtype) for x in kv]
    del kv
    row_bytes = D * pools[0].dtype.itemsize + (4 if scales else 0)

    for width in buckets:
        nb = width // bs
        tables = jnp.asarray(
            rng.permutation(N - 1)[:S * nb].reshape(S, nb) + 1, jnp.int32)
        ctx_np = contexts(args.contexts, S, width)
        ctx = jnp.asarray(ctx_np)
        need = int(ctx_np.sum()) * Hkv * row_bytes * 2

        def chained(attend):
            def run(q, k, v, sc):
                def body(_, q):
                    out = attend(q, k, v, sc)
                    return (q + out * 1e-3).astype(q.dtype)
                return lax.fori_loop(0, args.iters, body, q)
            return jax.jit(run)

        paths = [("gather", None, lambda q, k, v, sc: paged_attention(
            q, k, v, tables, ctx, width=width, impl="xla",
            window=args.window, **sc))]
        for keys in (args.block_keys or str(kernel._BLOCK_KEYS)).split(","):
            paths.append(("paged_kernel", int(keys),
                          lambda q, k, v, sc: kernel.paged_decode_attention(
                              q, k, v, tables, ctx, width=width,
                              window=args.window, **sc)))
        outs = {}
        for name, keys, attend in paths:
            pages = None
            if keys is not None:
                # read when a call is traced: every path is traced here
                kernel._BLOCK_KEYS = keys
                pages = kernel.block_pages(
                    bs, Hkv, D + -D % 128, pools[0].dtype.itemsize, N,
                    lane_rows=bool(scales) and dev.platform == "tpu")
            once = jax.jit(attend)(q, *pools, scales)
            outs[name] = np.asarray(once, np.float32)[ctx_np > 0]
            run = chained(attend)
            jax.block_until_ready(run(q, *pools, scales))     # compiles
            best = float("inf")
            for _ in range(args.repeats):
                t0 = time.perf_counter()
                jax.block_until_ready(run(q, *pools, scales))
                best = min(best, time.perf_counter() - t0)
            us = best / args.iters * 1e6
            line = {
                "path": name, "pages_per_block": pages, "bucket": width,
                "slots": S, "kv_heads": Hkv, "group": G, "head_dim": D,
                "block_size": bs, "dtype": args.dtype, "kv": args.kv,
                "window": args.window,
                "contexts": {"min": int(ctx_np.min()),
                             "max": int(ctx_np.max()),
                             "mean": float(ctx_np.mean())},
                "us_per_layer_call": us, "bytes_needed": need,
                "platform": dev.platform, "device_kind": dev.device_kind,
                "max_abs_diff_vs_gather": float(
                    np.abs(outs[name] - outs["gather"]).max()),
            }
            if peak is not None:
                floor_us = need / (peak["hbm_gbytes_per_s"] * 1e9) * 1e6
                line["roofline_us"] = floor_us
                line["hbm_roofline_share"] = 100.0 * floor_us / us
            else:
                line["note"] = ("no peak for this device: the time is no "
                                "accelerator's")
            print(json.dumps(line), flush=True)
            if args.out:
                with open(args.out, "a") as f:
                    f.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
