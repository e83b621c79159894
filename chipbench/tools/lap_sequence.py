"""A lap's step sequence, offline (PR 33): the closed loop of a serving
cell run for ``--steps`` engine iterations from an empty engine on the
configuration's REHEARSAL-size model under the cell's REAL engine geometry
(slots, block size, chunk, ``max_model_len``, the number of blocks the
real ``kv_pool_bytes`` buys at the real bytes a token) over the cell's
real lengths. The schedule of a lap does not depend on time or on the
model's size, so what this prints is what the chip will run: per
iteration the prefill dispatches (rows x bucket), the decode step's
slots and resident tokens, and the requests finished so far. From it
``laps.steps`` and ``trace_lap_iterations`` are chosen (README, "A
traffic mix").

    JAX_PLATFORMS=cpu python3 -m chipbench.tools.lap_sequence \\
        --workload <serving cell> --steps 200 --token-bytes 61440

``--token-bytes`` is one resident token's pool bytes at the published
widths (the rehearsal model's own are tiny); without it the rehearsal
deployment's pool is used as it is.
"""

from __future__ import annotations

import argparse
import dataclasses
import importlib
import json
import sys


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--steps", type=int, required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--token-bytes", type=int, default=0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    from chipbench import spec
    from chipbench.loadgen import make_requests
    from huggingface_sagemaker_tensorflow_distributed_tpu.serve.engine import (
        ServeEngine,
    )

    real = spec.load_cell(args.workload, False)
    tiny = spec.load_cell(args.workload, True)
    dep, loop = real.config["deployment"], real.traffic["loop"]
    family = importlib.import_module("chipbench.families."
                                     + tiny.config["family"])
    model, params = family.build(tiny.config, args.seed, dtype="float32")
    geometry = dict(num_slots=int(dep["num_slots"]),
                    block_size=int(dep["block_size"]),
                    prefill_chunk=int(dep["prefill_chunk"]),
                    max_model_len=int(dep["max_model_len"]))
    if args.token_bytes:
        geometry["num_blocks"] = 1 + int(dep["kv_pool_bytes"]) // (
            geometry["block_size"] * args.token_bytes)
    else:
        geometry["kv_pool_bytes"] = int(tiny.config["deployment"][
            "kv_pool_bytes"])
    # the tiny model's position table has to hold the real lengths
    model = type(model)(dataclasses.replace(
        model.config, max_position_embeddings=max(
            model.config.max_position_embeddings,
            geometry["max_model_len"])))
    eng = ServeEngine(model, params, **geometry)
    plans = make_requests(real.traffic, args.seed,
                          tiny.config["vocab_size"], 0.0)
    nxt, live, rows = 0, [], []

    def submit():
        nonlocal nxt
        live.append(eng.submit(plans[nxt].prompt, plans[nxt].max_new_tokens))
        nxt += 1

    for _ in range(min(int(loop["clients"]), len(plans))):
        submit()
    finished = 0
    for it in range(args.steps):
        d0, c0, n0 = eng.prefill_dispatches, eng.prefill_chunks, \
            eng.prefill_keys_needed
        a0, s0, t0 = eng.prefill_keys_attended, eng.decode_steps, \
            eng.tokens_generated
        eng.step()
        done = [r for r in live if r.finish_t is not None]
        live = [r for r in live if r.finish_t is None]
        finished += len(done)
        for _ in done:
            if nxt < len(plans):
                submit()
        ds = eng.sched.decode_slots()
        rows.append({
            "iteration": it,
            "prefill_dispatches": eng.prefill_dispatches - d0,
            "prefill_rows": eng.prefill_chunks - c0,
            "prefill_keys_needed": eng.prefill_keys_needed - n0,
            "prefill_keys_attended": eng.prefill_keys_attended - a0,
            "decode_dispatched": int(eng._pending is not None),
            "decode_steps_committed": eng.decode_steps - s0,
            "decode_slots": len(ds),
            "kv_tokens": sum(s.context_len for s in ds),
            "bucket": eng._bucket, "tokens": eng.tokens_generated - t0,
            "finished": finished, "preemptions": eng.sched.n_preemptions})
        print(json.dumps(rows[-1]), file=sys.stderr, flush=True)
    summary = {"workload": real.name, "steps": args.steps,
               "geometry": geometry, "num_blocks": eng.blocks.num_blocks,
               "tokens": eng.tokens_generated, "finished": finished,
               "prefill_dispatches": eng.prefill_dispatches,
               "decode_steps": eng.decode_steps, "iterations": rows}
    if args.out:
        with open(args.out, "w") as f:
            json.dump(summary, f, indent=1)
    print(json.dumps({k: v for k, v in summary.items()
                      if k != "iterations"}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
