"""One sub-layer's hyper-connection wrap WITHOUT its sub-layer, timed
alone on the chip, by hand (PR 35): the norm over a token's ``n x C``
values, ``xbar Phi`` at ``HIGHEST``, the sigmoids, the exponential, 20
Sinkhorn iterations, and the three mixes (``H_pre x``, ``H_res x``,
``H_post^T y``), through the program's own functions
(``models/xing4.py::mhc_maps`` / ``mhc_merge``).

    python3 -m chipbench.tools.mhc_microbench --tokens 64,2048 --out <file.jsonl>

One JSON line a token count (64: a decode step's slots; 2,048: a four-row
prefill dispatch): microseconds a wrap; the bytes it NEEDS (the streams
read once for the maps and the first mix, read once more and written once
for the merge, the sub-layer's input written and its output read, ``Phi``
once: ``bytes_needed``) with the GB/s they make and their share of the
device's memory peak (``chipbench/peaks.json``). The wrap's operations
have no name in a TPU trace (XLA fusions carry none), so this is how
their cost gets on record: 12 wraps a decode step of the six-layer cut
beside ``decode_step_device_ms``.

The time is the device's: ``--iters`` wraps chained inside ONE jitted
program, each wrap's streams the last wrap's output (a changing input
each call, and a result the next call consumes: nothing is hoisted or
dropped; the stand-in for the sub-layer is ``y = u``), the program timed
on the host's clock around ``block_until_ready``, the best of
``--repeats``. Off a TPU it runs at whatever size it is given and says
so: its times are then no device's."""

from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tokens", default="64,2048")
    ap.add_argument("--streams", type=int, default=4)
    ap.add_argument("--hidden", type=int, default=3584)
    ap.add_argument("--sinkhorn-iters", type=int, default=20)
    ap.add_argument("--dtype", default="bfloat16",
                    choices=("bfloat16", "float32"))
    ap.add_argument("--iters", type=int, default=24)
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from chipbench import arith
    from huggingface_sagemaker_tensorflow_distributed_tpu.models.xing4 import (
        mhc_maps,
        mhc_merge,
    )

    dev = jax.devices()[0]
    on_chip = dev.platform == "tpu"
    dt = jnp.dtype(args.dtype)
    n, c = args.streams, args.hidden
    rng = np.random.default_rng(0)
    phi = jnp.asarray(rng.normal(0, 0.02, (n * c, 2 * n + n * n)), dt)
    alpha = jnp.full((3,), 0.01, jnp.float32)
    b_pre = jnp.full((n,), -np.log(n - 1.0), jnp.float32)
    b_post = jnp.zeros((n,), jnp.float32)
    b_res = 8.0 * jnp.eye(n, dtype=jnp.float32)

    def wrap(x):
        h_pre, h_post, h_res = mhc_maps(
            x, phi, alpha, b_pre, b_post, b_res, iters=args.sinkhorn_iters,
            eps=1e-6, clamp=(-30.0, 30.0))
        u = jnp.sum(h_pre[..., None] * x.astype(jnp.float32),
                    axis=-2).astype(dt)
        # the sub-layer's stand-in: y = u, scaled so the chain stays finite
        return mhc_merge(x, (h_post, h_res), u * 0.5)

    @jax.jit
    def run(x):
        return jax.lax.fori_loop(0, args.iters, lambda _, x: wrap(x), x)

    lines = []
    for tokens in (int(t) for t in args.tokens.split(",")):
        x = jnp.asarray(rng.normal(size=(1, tokens, n, c)), dt)
        jax.block_until_ready(run(x))                       # compiles
        best = float("inf")
        for _ in range(args.repeats):
            t0 = time.perf_counter()
            jax.block_until_ready(run(x))
            best = min(best, time.perf_counter() - t0)
        item = dt.itemsize
        # streams: read for the maps and H_pre x, read and written by the
        # merge; u written, y read; Phi once
        need = item * (tokens * c * (3 * n + 2) + phi.size)
        line = {"tokens": tokens, "streams": n, "hidden": c,
                "dtype": args.dtype, "platform": dev.platform,
                "device_kind": dev.device_kind, "on_chip": on_chip,
                "iters": args.iters,
                "us_per_wrap": 1e6 * best / args.iters,
                "bytes_needed": int(need),
                "gbytes_per_s": need / (best / args.iters) / 1e9}
        if on_chip:
            bw = arith.peaks(dev.device_kind)["hbm_gbytes_per_s"]
            line["hbm_peak_share"] = 100.0 * line["gbytes_per_s"] / bw
        lines.append(line)
        print(json.dumps(line), flush=True)
    if args.out:
        with open(args.out, "a") as f:
            for line in lines:
                f.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
