"""One linear-attention layer's ONE-TOKEN step of the gated delta rule,
timed alone on the chip: the fused kernel
(``ops/pallas_gated_delta.py``) against the jnp form
(``ops/gated_delta.py::gated_delta_step``, two XLA fusions over the
state), each on the pool as the shapes count it (``[B, H, dk, dv]``: 192
lanes are padded to 256 in HBM) and on the whole-tile pool
(``state_layout``: ``[B, H/G, dk, G*dv]``), by hand (PR 36).

    python3 -m chipbench.tools.state_step_microbench \\
        --blocks 3,5,15 --out <file.jsonl>

One JSON line a (form, pool): milliseconds a call; the bytes the call
NEEDS (the state read once and written once, as
``chipbench/arith_olmo_hybrid.py`` counts a decode step's state) and the
bytes it MOVED (the passes the form makes, 3 for the jnp form and 2 for
the kernel, over the pool as it lies in ``(8, 128)`` tiles), with the GB/s
each makes and the needed bytes' time at the device's memory peak
(``chipbench/peaks.json``) as a share of the time measured; the largest
difference of the call's output and state from the jnp form's on the
plain pool. Shapes default to ``olmo-hybrid-7b-pp2-gen-sat``'s: 64 slots,
30 heads of ``96 x 192``, float32.

The time is the device's: ``--iters`` calls chained inside ONE jitted
program, the STATE carried from call to call and the next call's ``q``,
``k`` and ``v`` made from the last call's output, so nothing of a call
can be hoisted out of the loop (a loop whose operands did not change
from call to call was timed without its largest pass: ROADMAP S10 (8));
the program timed on the host's clock around ``block_until_ready``, the
best of ``--repeats``. Off a TPU it runs the kernel in interpret mode at
whatever size it is given and says so: its times are then no device's."""

from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np


def tiled_bytes(shape, itemsize: int = 4) -> int:
    """Bytes of an array whose two minor dimensions lie in ``(8, 128)``
    tiles (32-bit: what the v5e's compiler gives a float32 pool)."""
    *lead, rows, lanes = shape
    return (int(np.prod(lead, dtype=np.int64)) * -(-rows // 8) * 8
            * -(-lanes // 128) * 128 * itemsize)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--slots", type=int, default=64)
    ap.add_argument("--heads", type=int, default=30)
    ap.add_argument("--dk", type=int, default=96)
    ap.add_argument("--dv", type=int, default=192)
    ap.add_argument("--blocks", default=None,
                    help="sweep the kernel's packed rows a grid step on the "
                         "whole-tile pool: a list. Default: as it ships")
    ap.add_argument("--iters", type=int, default=50)
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from jax import lax

    from chipbench import arith
    from huggingface_sagemaker_tensorflow_distributed_tpu.ops import (
        pallas_gated_delta as fused,
    )
    from huggingface_sagemaker_tensorflow_distributed_tpu.ops.gated_delta import (
        gated_delta_step,
        l2_normalize,
    )

    dev = jax.devices()[0]
    try:
        peak = arith.peaks(dev.device_kind)
    except LookupError:
        peak = None                     # the CPU rehearsal: no device's time
    B, H, dk, dv = args.slots, args.heads, args.dk, args.dv
    rng = np.random.RandomState(args.seed)

    def f32(*shape):
        return jnp.asarray(rng.randn(*shape), jnp.float32)

    q, k, v = f32(B, H, dk), f32(B, H, dk), f32(B, H, dv)
    g = -jax.nn.softplus(f32(B, H))
    beta = 2.0 * jax.nn.sigmoid(f32(B, H))
    # one inactive slot, as a step of the cell has now and then
    mask = jnp.arange(B) != B - 1
    state = f32(B, H, dk, dv)
    plain, whole = (B, H, dk, dv), (B,) + fused.state_layout(H, dk, dv)

    def xla_plain(q, k, v, s):
        return gated_delta_step(q, k, v, g, beta, s, mask)

    def xla_whole(q, k, v, s):
        o, s = gated_delta_step(q, k, v, g, beta, fused.unpack(s, H), mask)
        return o, fused.pack(s)

    def kernel(block):
        return lambda q, k, v, s: fused.gated_delta_step_packed(
            q, k, v, g, beta, s, mask, block=block)

    forms = [("xla", "plain", None, 3, xla_plain, plain),
             ("xla", "whole_tile", None, 3, xla_whole, whole),
             ("kernel", "plain", None, 2, kernel(None), plain)]
    for block in ([int(b) for b in args.blocks.split(",")]
                  if args.blocks else [None]):
        forms.append(("kernel", "whole_tile", block, 2, kernel(block),
                      whole))

    def chained(step):
        def run(s, o):
            def body(_, carry):
                s, o = carry
                # the next call's operands hang on the last call's output
                nudge = 1e-3 * o[..., :dk]
                return step(l2_normalize(q + nudge) * dk ** -0.5,
                            l2_normalize(k + nudge), v + 1e-3 * o, s)[::-1]
            return lax.fori_loop(0, args.iters, body, (s, o))
        return jax.jit(run, donate_argnums=0)

    need = 2 * B * H * dk * dv * 4
    want = None
    for form, pool, block, passes, step, shape in forms:
        def fresh():
            s = state if shape == plain else fused.pack(state)
            return jnp.copy(s), jnp.zeros((B, H, dv), jnp.float32)

        run = chained(step)
        try:
            s_out, o_out = jax.block_until_ready(run(*fresh()))  # compiles
        except Exception as e:  # noqa: BLE001 - the compiler's refusal
            print(json.dumps({"form": form, "pool": pool,
                              "block_rows": block,
                              "refused": str(e)[:400]}), flush=True)
            continue
        got = (np.asarray(o_out), np.asarray(
            s_out if shape == plain else fused.unpack(s_out, H)))
        want = want or got
        best = float("inf")
        for _ in range(args.repeats):
            operands = jax.block_until_ready(fresh())
            t0 = time.perf_counter()
            jax.block_until_ready(run(*operands))
            best = min(best, time.perf_counter() - t0)
        ms = best / args.iters * 1e3
        moved = passes * tiled_bytes(shape)
        line = {
            "form": form, "pool": pool, "pool_shape": list(shape),
            "block_rows": (block or fused.block_rows(*shape[1:])
                           if form == "kernel" else None),
            "slots": B, "heads": H, "dk": dk, "dv": dv,
            "ms_per_call": ms, "bytes_needed": need, "bytes_moved": moved,
            "pool_bytes_in_tiles": tiled_bytes(shape),
            "gbytes_per_s_needed": need / ms / 1e6,
            "gbytes_per_s_moved": moved / ms / 1e6,
            "platform": dev.platform, "device_kind": dev.device_kind,
            "max_abs_diff_o": float(np.abs(got[0] - want[0]).max()),
            "max_abs_diff_state": float(np.abs(got[1] - want[1]).max()),
        }
        if peak is not None:
            line["hbm_peak_share_needed"] = (
                100.0 * need / (peak["hbm_gbytes_per_s"] * 1e6) / ms)
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
