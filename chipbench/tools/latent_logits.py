"""Logits against logits, once, by hand (PR 28): the program's CHUNKED
PREFILL and DECODE STEPS through the paged cache, teacher-forced over a
seeded sequence, against the reference's full forward over the same
tokens.

    python3 -m chipbench.tools.latent_logits --workload <serving cell> \\
        --seed <n> --lengths 2048,8192 --decode 32 --out <file.json>

For each length: the largest absolute difference of a logit, how far
under the reference's maximum the program's argmax lies (the quantity the
cell's check compares with ``token_margin``: worst, 99th percentile, the
share above 0), and for a configuration with routed experts the share of
(token, expert layer) pairs whose set of experts differs from the
reference's. ``--below`` names dtypes: the REFERENCE computed with every
matmul operand rounded through each, read the same way (what the next
precision below the configuration's would give: it has to come out as not
correct). ``--plain`` adds the program's plain forward (no cache) in
float32 at ``highest`` precision over the first length: a difference there
is a difference of mathematics, not of rounding.

``--control <n>`` (with ``--seeds a,b,..`` and ``--below <dtype>``) takes
the two readings a ``token_margin`` is set between AT THE SIZE OF THE
CELL'S OWN CHECK: for each seed, the engine at the cell's deployment
serves the first ``n`` requests of the cell's traffic greedily (answers
cut at ``--answer`` tokens, what a lap of this cell lets a request
finish), and every request is read as ``kinds/serve.py::_check`` reads
it (the reference's teacher-forced logits over prompt + answer, the gap
of each answer token under the reference's maximum, the worst over the
request), once for the token the program chose and once, at the same
positions and under the same prefix, for the token the reference
computed in ``<dtype>`` would choose. A check of ``k`` requests passes a
limit when all ``k`` worst gaps lie under it; the result holds every
request's two worst gaps, so that share is counted, not fitted.

Drives the engine's own jitted steps for the state (``_prefill_chunk``,
``_decode_step``) and reads the logits from one more apply over the same
assembled cache: the engine hands out tokens, not logits."""

from __future__ import annotations

import argparse
import dataclasses
import importlib
import json
import sys

import numpy as np


def _gaps(ref: np.ndarray, got: np.ndarray) -> dict:
    idx = np.arange(len(ref))
    gap = ref.max(-1) - ref[idx, got.argmax(-1)]
    return {"max_abs_diff": float(np.abs(ref - got).max()),
            "mean_abs_diff": float(np.abs(ref - got).mean()),
            "worst_gap": float(gap.max()),
            "gap_p99": float(np.percentile(gap, 99)),
            "argmax_differs_share": float((gap > 0).mean()),
            "ref_logit_std": float(ref.std())}


def program_logits(model, params, dep: dict, tokens: np.ndarray, n_decode: int):
    """Float32 logits ``[len(tokens), vocab]`` of the program: chunked
    prefill over all but the last ``n_decode`` tokens, then one decode
    step a token, through paged pools; and the experts it chose,
    ``[expert layers, len(tokens), k]`` (None without routed experts)."""
    import jax
    import jax.numpy as jnp

    from huggingface_sagemaker_tensorflow_distributed_tpu.serve import engine as E

    C, bs, max_len = dep["prefill_chunk"], dep["block_size"], dep["max_model_len"]
    eng = E.ServeEngine(model, params, num_slots=1, block_size=bs,
                        prefill_chunk=C, max_model_len=max_len,
                        num_blocks=2 + max_len // bs)
    plan, pools, nb = eng._plan, eng._pools, eng.max_blocks_per_seq
    table = np.arange(1, nb + 1, dtype=np.int32)[None]
    routes = E._routes(model)
    zf, zi = np.zeros((1,), np.float32), np.zeros((1,), np.int32)
    samp = (zf, zi, zf, np.zeros((1, 2), np.uint32), zi)

    def ids_of(mut):
        if not routes:
            return None
        flat = jax.tree_util.tree_flatten_with_path(mut["moe_stats"])[0]
        return jnp.stack([leaf[0] for path, leaf in flat if any(
            getattr(p, "key", None) == "expert_ids" for p in path)])

    @jax.jit
    def peek(params, pools, toks, start, width_valid):
        # the apply inside the engine's step, for its logits
        width = width_valid.shape[1]
        cache = E._assemble_cache(plan, pools, table, start, width=width)
        n = toks.shape[1]
        pos = start[:, None] + jnp.arange(n, dtype=jnp.int32)[None]
        lg, mut = model.apply(
            {"params": params, "cache": cache}, toks, width_valid,
            position_ids=pos, decode=True, deterministic=True,
            mutable=["cache", "moe_stats"] if routes else ["cache"])
        return lg[0].astype(jnp.float32), ids_of(mut)

    prefill = E._prefill_chunk_jit(False)
    decode = E._decode_step_jit(False)
    n_prompt = len(tokens) - n_decode
    assert 0 < n_prompt and len(tokens) <= max_len
    out, ids = [], []
    for s in range(0, n_prompt, C):
        # the prompt's last chunk may be short: padded to the chunk as the
        # engine pads it (the pad tail's rows are written past the prompt
        # and overwritten by the decode steps before any query sees them)
        real = min(C, n_prompt - s)
        width = next(b for b in eng.prefill_buckets if b >= s + C)
        chunk = np.zeros((1, C), np.int32)
        chunk[0, :real] = tokens[s:s + real]
        start = np.array([s], np.int32)
        valid = (np.arange(width)[None] < s + C).astype(np.int32)
        lg, i = peek(params, pools, chunk, start, valid)
        _, pools, *_ = prefill(model, params, pools, chunk, table, start,
                               np.array([real - 1], np.int32), *samp, plan,
                               False, width)
        out.append(np.asarray(lg)[:real])
        ids.append(None if i is None else i[:, :real])
    for t in range(n_prompt, len(tokens)):
        width = next(b for b in eng.gather_buckets if b >= t + 1)
        tok, ctx = tokens[None, t:t + 1], np.array([t], np.int32)
        valid = (np.arange(width)[None] <= t).astype(np.int32)
        lg, i = peek(params, pools, tok, ctx, valid)
        _, pools, *_ = decode(model, params, pools, tokens[t:t + 1], table,
                              ctx, np.ones((1,), bool), *samp, plan, width,
                              False)
        out.append(np.asarray(lg)), ids.append(i)
    ids = (np.concatenate([np.asarray(i) for i in ids], axis=1)
           if routes else None)
    return np.concatenate(out, axis=0), ids


def routing_differs_share(ids: np.ndarray, chosen: list) -> list:
    """Per expert layer: the share of tokens whose set of experts (the
    program's ``ids`` [layers, S, k]) is not the reference's (``chosen``
    [S, experts] bool a layer)."""
    out = []
    for layer_ids, mask in zip(ids, chosen):
        mask = np.asarray(mask)[:layer_ids.shape[0]]
        mine = np.zeros_like(mask)
        np.put_along_axis(mine, layer_ids, True, axis=1)
        out.append(float((mine != mask).any(axis=1).mean()))
    return out


def check_size_control(cell, model, params, ref, seeds: list, n: int,
                       answer: int, below: str) -> list:
    """Per seed and request: the answer's length, the worst gap of the
    program's tokens and of the tokens ``below`` would choose, both under
    the reference's own maximum (see the module's text)."""
    import jax.numpy as jnp

    from chipbench.kinds import serve
    from chipbench.loadgen import make_requests

    cfg = cell.config
    eng = serve.build_engine(model, params, cfg["deployment"])
    eng.warmup()
    out = []
    for seed in seeds:
        plans = make_requests(cell.traffic, seed, cfg["vocab_size"], 0.0)[:n]
        reqs = [eng.submit(p.prompt, min(p.max_new_tokens, answer))
                for p in plans]
        eng.run()
        for plan, req in zip(plans, reqs):
            ans = np.asarray(eng.output_ids(req))
            seq = np.concatenate([plan.prompt, ans]).astype(np.int32)
            tokens = jnp.asarray(np.pad(seq, (0, -len(seq) % 512)))
            rows = jnp.arange(len(plan.prompt) - 1, len(seq) - 1)
            want = np.asarray(ref.logits(params, cfg, tokens, rows))
            low = np.asarray(ref.logits(params, cfg, tokens, rows,
                                        compute=below))
            at = np.arange(len(ans))
            top = want.max(-1)
            row = {"seed": seed, "prompt": len(plan.prompt),
                   "answer": len(ans),
                   "program_worst_gap": float((top - want[at, ans]).max()),
                   "control_worst_gap": float(
                       (top - want[at, low.argmax(-1)]).max())}
            out.append(row)
            print(f"latent_logits: control {json.dumps(row)}",
                  file=sys.stderr, flush=True)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--lengths", default="2048,8192")
    ap.add_argument("--decode", type=int, default=32)
    ap.add_argument("--below", default="")
    ap.add_argument("--plain", type=int, default=0)
    ap.add_argument("--control", type=int, default=0)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--answer", type=int, default=80)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from chipbench import device, spec

    cell = spec.load_cell(args.workload, device.asked_for_cpu())
    cfg, dep = cell.config, cell.config["deployment"]
    family = importlib.import_module("chipbench.families." + cfg["family"])
    ref = importlib.import_module("chipbench.reference." + cfg["family"])
    model, params = family.build(cfg, args.seed, dtype=dep["dtype"])
    result = {"workload": cell.name, "seed": args.seed,
              "platform": jax.devices()[0].platform, "lengths": {}}
    if args.control:
        result["control"] = {
            "below": args.below, "answer": args.answer,
            "requests": check_size_control(
                cell, model, params, ref,
                [int(x) for x in args.seeds.split(",")], args.control,
                args.answer, args.below)}
    rng = np.random.default_rng(args.seed)
    first = True
    for n in (int(x) for x in filter(None, args.lengths.split(","))):
        tokens = rng.integers(3, cfg["vocab_size"], size=n, dtype=np.int32)
        got, ids = program_logits(model, params, dep, tokens, args.decode)
        padded = jnp.asarray(np.pad(tokens, (0, -n % 512)))
        rows, chosen = jnp.arange(n), []
        want = np.asarray(ref.logits(params, cfg, padded, rows,
                                     routing_out=chosen))
        r = {"all": _gaps(want, got),
             "decode_steps": _gaps(want[-args.decode:], got[-args.decode:])}
        if ids is not None:
            r["routing_differs_share_by_layer"] = routing_differs_share(
                ids, chosen)
        if first:
            for name in filter(None, args.below.split(",")):
                low = np.asarray(ref.logits(params, cfg, padded, rows,
                                            compute=name))
                r["reference_in_" + name] = _gaps(want, low)
            if args.plain:
                plain = type(model)(dataclasses.replace(
                    model.config, dtype=jnp.float32))
                with jax.default_matmul_precision("highest"):
                    lg = jax.jit(lambda p, t: plain.apply({"params": p}, t))(
                        params, jnp.asarray(tokens)[None])[0]
                r["plain_float32_forward"] = _gaps(want, np.asarray(lg))
        first = False
        result["lengths"][str(n)] = r
        print(f"latent_logits: {n} {json.dumps(r)}", file=sys.stderr,
              flush=True)
    text = json.dumps(result, indent=1)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
