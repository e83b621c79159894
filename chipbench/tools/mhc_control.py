"""The readings a ``token_margin`` is set between, for a configuration
whose residual path is hyper-connections (PR 35), AT THE SIZE OF THE
CELL'S OWN CHECK:

    python3 -m chipbench.tools.mhc_control --workload <serving cell> \\
        --seeds a,b,.. --requests 12 --answer 170 --out <file.json>

As ``tools/state_control`` does for a configuration with recurrent state:
for each seed the engine at the cell's deployment serves the first
``--requests`` requests of the cell's traffic greedily (answers cut at
``--answer`` tokens, about what a lap of the cell lets a request finish),
and every request is read as ``kinds/serve.py::_check`` reads it: the
reference's teacher-forced float32 logits over prompt + answer, the gap of
each answer token under the reference's maximum, the worst over the
request. Three times: for the token the PROGRAM chose; for the token the
reference computed with every matmul operand of the sub-layers rounded
through ``--below`` (float8_e4m3fn: the precision under the
configuration's bf16) would choose, at the same positions under the same
prefix; and for the token the reference with the wrap's MAPS (``xbar
Phi``, the exponential, every Sinkhorn iteration) rounded through
``--maps`` (bfloat16) would choose. Beside them the largest ``|row or
column sum - 1|`` of an ``H_res`` the reference made. A check of ``k``
requests passes a limit when all ``k`` worst gaps lie under it. The
weights are ``--seed``'s; the traffic's tokens each of ``--seeds``'."""

from __future__ import annotations

import argparse
import gc
import importlib
import json
import sys

import numpy as np


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--answer", type=int, default=170)
    ap.add_argument("--below", default="float8_e4m3fn")
    ap.add_argument("--maps", default="bfloat16")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from chipbench import device, spec
    from chipbench.kinds import serve
    from chipbench.loadgen import make_requests

    cell = spec.load_cell(args.workload, device.asked_for_cpu())
    cfg = cell.config
    family = importlib.import_module("chipbench.families." + cfg["family"])
    ref = importlib.import_module("chipbench.reference." + cfg["family"])
    model, params = family.build(cfg, args.seed,
                                 dtype=cfg["deployment"]["dtype"])
    eng = serve.build_engine(model, params, cfg["deployment"])
    eng.warmup()
    served = []
    for seed in (int(x) for x in args.seeds.split(",")):
        plans = make_requests(cell.traffic, seed, cfg["vocab_size"],
                              0.0)[:args.requests]
        reqs = [eng.submit(p.prompt, min(p.max_new_tokens, args.answer))
                for p in plans]
        eng.run()
        served.append((seed, plans, [np.asarray(eng.output_ids(r))
                                     for r in reqs]))
    # the engine's pools go before the reference's float32 layers come
    jax.block_until_ready(jax.live_arrays())
    del eng, reqs
    gc.collect()
    rows = []
    for seed, plans, answers in served:
        for plan, ans in zip(plans, answers):
            seq = np.concatenate([plan.prompt, ans]).astype(np.int32)
            tokens = jnp.asarray(np.pad(seq, (0, -len(seq) % 512)))
            at = jnp.arange(len(plan.prompt) - 1, len(seq) - 1)
            defects = []
            want = np.asarray(ref.logits(params, cfg, tokens, at,
                                         defect_out=defects))
            low = np.asarray(ref.logits(params, cfg, tokens, at,
                                        compute=args.below))
            maps = np.asarray(ref.logits(params, cfg, tokens, at,
                                         maps_compute=args.maps))
            i, top = np.arange(len(ans)), want.max(-1)
            row = {"seed": seed, "prompt": len(plan.prompt),
                   "answer": len(ans),
                   "program_worst_gap": float((top - want[i, ans]).max()),
                   "program_argmax_flips": int((want.argmax(-1) != ans).sum()),
                   "below_worst_gap": float(
                       (top - want[i, low.argmax(-1)]).max()),
                   "maps_worst_gap": float(
                       (top - want[i, maps.argmax(-1)]).max()),
                   "maps_max_abs_diff": float(np.abs(maps - want).max()),
                   "reference_defect_max": max(float(d) for d in defects),
                   "ref_logit_std": float(want.std())}
            rows.append(row)
            print(f"mhc_control: {json.dumps(row)}", file=sys.stderr,
                  flush=True)
    by_seed = {}
    for r in rows:
        by_seed.setdefault(r["seed"], []).append(r)
    result = {"workload": cell.name, "weights_seed": args.seed,
              "platform": jax.devices()[0].platform, "below": args.below,
              "maps": args.maps, "answer": args.answer,
              "per_check": [{"seed": s, **{
                  k: max(r[k] for r in rs) for k in (
                      "program_worst_gap", "below_worst_gap",
                      "maps_worst_gap", "reference_defect_max")}}
                  for s, rs in by_seed.items()],
              "requests": rows}
    text = json.dumps(result, indent=1)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text)
    print(json.dumps({k: v for k, v in result.items() if k != "requests"}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
