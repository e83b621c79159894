"""One decode layer-call of LATENT attention over a paged cache, timed
alone on the chip: the fused kernel
(``ops/pallas_paged_latent_attention.py``) against the gather path (a
bucket-wide copy of the cache through the block tables, the step's row
written into it, ``models/deepseek_v2.py::attend_absorbed`` over it), by
hand (PR 34).

    python3 -m chipbench.tools.latent_decode_microbench \\
        --buckets 8192 --contexts 600:7600:2860,8192 --out <file.jsonl>

One JSON line a (bucket, contexts, path): milliseconds a layer-call; the
bytes the call NEEDS (a row of ``rank + rope`` values a resident token,
read once) with the GB/s they make and their time at the device's memory
peak; the FLOPs it needs (``2 x heads x (2 rank + rope)`` a resident
token: scores over the row, the sum over its first ``rank`` values;
``chipbench/arith_deepseek_v2.py`` counts a decode step's attention so)
with their time at the device's bf16 peak (``chipbench/peaks.json``);
each as a share of the time measured, and the larger one as the call's
share of its roofline; the largest difference between the two paths'
outputs. Both paths start from the absorbed query and end at the weighted
sum in the latent space: what comes before and after is the same in both
steps. Shapes default to ``deepseek-v2-ep4-doc-sat``'s: 32 slots, 128
heads, rank 512, 64 rotary, rows of 640 in pages of 16, a pool of 19,532
pages, bf16. ``--contexts`` is a comma list, each ``n`` (every slot) or
``min:max:mean`` (spread geometrically, bent to the mean:
``paged_decode_microbench.contexts``); doc-sat's traced steps hold 2,860
tokens a slot in the mean, all at the 8,192 bucket.

The time is the device's: ``--iters`` calls chained inside ONE jitted
program (each call's query depends on the last call's output), the
program timed on the host's clock around ``block_until_ready``, the best
of ``--repeats``. Off a TPU it runs the kernel in interpret mode at
whatever size it is given and says so: its times are then no device's."""

from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--slots", type=int, default=32)
    ap.add_argument("--heads", type=int, default=128)
    ap.add_argument("--rank", type=int, default=512)
    ap.add_argument("--rope", type=int, default=64)
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--buckets", default="8192")
    ap.add_argument("--contexts", default="600:7600:2860,8192")
    ap.add_argument("--dtype", default="bfloat16",
                    choices=("bfloat16", "float32"))
    ap.add_argument("--pool-blocks", type=int, default=19532,
                    help="pages of one layer's pool (19,532: doc-sat's), "
                         "or as many as the widest bucket's tables need")
    ap.add_argument("--block-keys", default=None,
                    help="sweep the kernel's compute block (the K/V "
                         "kernel's module constant _BLOCK_KEYS, which it "
                         "shares): a list of keys. Default: as it ships")
    ap.add_argument("--iters", type=int, default=50)
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from jax import lax

    from chipbench import arith
    from chipbench.tools.paged_decode_microbench import contexts
    from huggingface_sagemaker_tensorflow_distributed_tpu.models import (
        deepseek_v2 as model,
    )
    from huggingface_sagemaker_tensorflow_distributed_tpu.ops import (
        pallas_paged_attention as paged,
    )
    from huggingface_sagemaker_tensorflow_distributed_tpu.ops.attention import (
        gather_paged_kv,
    )
    from huggingface_sagemaker_tensorflow_distributed_tpu.ops.pallas_paged_latent_attention import (
        paged_latent_decode_attention,
    )

    dev = jax.devices()[0]
    try:
        peak = arith.peaks(dev.device_kind)
    except LookupError:
        peak = None                     # the CPU rehearsal: no device's time
    S, H, rank, rope, bs = (args.slots, args.heads, args.rank, args.rope,
                            args.block_size)
    row = -(-(rank + rope) // 128) * 128
    dtype = jnp.dtype(args.dtype)
    scale = (128 + rope) ** -0.5
    rng = np.random.RandomState(args.seed)
    buckets = [int(b) for b in args.buckets.split(",")]
    N = max(args.pool_blocks, S * max(buckets) // bs + 1)
    q = jnp.asarray(rng.randn(S, H, row) * 0.1, dtype).at[
        ..., rank + rope:].set(0)
    pool = jnp.zeros((N, bs, row), dtype).at[..., :rank + rope].set(
        jnp.asarray(rng.randn(N, bs, rank + rope) * 0.3, dtype))
    new_row = pool.reshape(-1, row)[:S]     # the step's row of each slot

    def gather(q, pool, tables, ctx, width):
        """The gather step's attention: the bucket's pages copied out,
        the step's row written at each slot's context, both passes of
        the absorbed form over the copy. ``ctx`` counts the row."""
        latent = gather_paged_kv(pool[:, :, None, :], tables,
                                 width=width)                # [S, 1, W, row]
        latent = jax.vmap(lambda b, new, i: lax.dynamic_update_slice(
            b, new, (0, i, 0)))(latent, new_row[:, None, None], ctx - 1)[:, 0]
        bias = model.mask_bias(ctx - 1, 1, None, width)
        scores = jnp.einsum("bshc,bwc->bhsw", q[:, None], latent,
                            preferred_element_type=jnp.float32) * scale
        p = jax.nn.softmax(scores + bias[:, None], axis=-1).astype(dtype)
        return jnp.einsum("bhsw,bwr->bshr", p, latent[..., :rank])[:, 0]

    def kernel(q, pool, tables, ctx, width):
        return paged_latent_decode_attention(
            q, pool, tables[:, :width // bs], ctx, rank=rank, scale=scale)

    def chained(attend, *static):
        def run(q, pool, tables, ctx):
            def body(_, q):
                # the tables hang on the carry (plus a zero the compiler
                # cannot fold), or the gather's copy of the cache, which
                # is the same in every call, is hoisted out of the loop
                # and the path is timed without it
                same = tables + (q[0, 0, 0] * 0).astype(tables.dtype)
                out = attend(q, pool, same, ctx, *static)
                return q.at[..., :rank].add((out * 1e-3).astype(q.dtype))
            return lax.fori_loop(0, args.iters, body, q)
        return jax.jit(run)

    for width in buckets:
        nb = width // bs
        tables = jnp.asarray(
            rng.permutation(N - 1)[:S * nb].reshape(S, nb) + 1, jnp.int32)
        for spec in args.contexts.split(","):
            ctx_np = np.maximum(contexts(spec, S, width), 1)
            ctx = jnp.asarray(ctx_np)
            # the rows the kernel reads hold the step's row already
            written = pool.at[tables[jnp.arange(S), (ctx - 1) // bs],
                              (ctx - 1) % bs].set(new_row)
            tokens = int(ctx_np.sum())
            need_bytes = tokens * (rank + rope) * dtype.itemsize
            need_flops = 2.0 * H * (2 * rank + rope) * tokens
            paths = [("gather", None, gather, pool)]
            for keys in (args.block_keys
                         or str(paged._BLOCK_KEYS)).split(","):
                # a function of its own a block size: jit caches by function
                paths.append(("paged_kernel", int(keys),
                              lambda *a: kernel(*a), written))
            outs = {}
            for name, keys, attend, cache in paths:
                if keys is not None:
                    # read when a call is traced: every path is traced here
                    paged._BLOCK_KEYS = keys
                outs[name] = np.asarray(
                    jax.jit(attend, static_argnums=4)(
                        q, cache, tables, ctx, width), np.float32)
                run = chained(attend, width)
                jax.block_until_ready(run(q, cache, tables, ctx))  # compiles
                best = float("inf")
                for _ in range(args.repeats):
                    t0 = time.perf_counter()
                    jax.block_until_ready(run(q, cache, tables, ctx))
                    best = min(best, time.perf_counter() - t0)
                ms = best / args.iters * 1e3
                line = {
                    "path": name, "block_keys": keys, "bucket": width,
                    "slots": S, "heads": H, "rank": rank, "rope": rope,
                    "row": row, "block_size": bs, "dtype": args.dtype,
                    "contexts": {"min": int(ctx_np.min()),
                                 "max": int(ctx_np.max()),
                                 "mean": float(ctx_np.mean())},
                    "ms_per_layer_call": ms, "bytes_needed": need_bytes,
                    "flops_needed": need_flops,
                    "gbytes_per_s": need_bytes / ms / 1e6,
                    "platform": dev.platform, "device_kind": dev.device_kind,
                    "max_abs_diff_vs_gather": float(
                        np.abs(outs[name] - outs["gather"]).max()),
                }
                if peak is not None:
                    hbm_ms = need_bytes / (peak["hbm_gbytes_per_s"] * 1e6)
                    mxu_ms = need_flops / (peak["bf16_tflops"] * 1e9)
                    line["hbm_peak_share"] = 100.0 * hbm_ms / ms
                    line["mxu_peak_share"] = 100.0 * mxu_ms / ms
                    line["roofline_ms"] = max(hbm_ms, mxu_ms)
                    line["roofline_share"] = 100.0 * max(hbm_ms, mxu_ms) / ms
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
