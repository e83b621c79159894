"""Deterministic serving gates, one engine run a case: after
``warmup()`` no mechanism mints a compile, sharding or a second replica
doubles the admission depth on the same per-device pool bytes, the
gather ladder reads less padding than full width, int8 pools read at
most 0.6 of the fp bytes a step, a perfect draft fills the speculative
window, and ``policy="slo"`` misses fewer deadlines than ``fifo`` on
the same schedule. Token identity per mechanism is held where the
mechanism is tested (``test_serve.py``, ``test_router.py``,
``test_transport.py``, ``test_loadgen.py``, ``test_policy.py``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from huggingface_sagemaker_tensorflow_distributed_tpu import obs
from huggingface_sagemaker_tensorflow_distributed_tpu.serve.engine import (
    ServeEngine,
)
from huggingface_sagemaker_tensorflow_distributed_tpu.serve.loadgen import (
    OpenLoopDriver,
    SloSpec,
    make_schedule,
)
from huggingface_sagemaker_tensorflow_distributed_tpu.serve.router import (
    Router,
)


# contexts 14, 21 and 20 tokens against a 16-wide first bucket: the run
# switches buckets mid-stream
_GEOM = dict(num_slots=3, block_size=4, num_blocks=25, prefill_chunk=8,
             max_model_len=32, gather_buckets=[16, 32])
_LENGTHS = [(5, 9), (15, 6), (12, 8), (7, 5), (10, 7), (4, 11)]


def _mixed_trace(seed=31, lengths=_LENGTHS):
    rng = np.random.RandomState(seed)
    return [(rng.randint(1, 120, (p,)).astype(np.int32), m)
            for p, m in lengths]


def _shared_prefix_trace():
    rng = np.random.RandomState(3)
    prefix = rng.randint(1, 120, (12,)).astype(np.int32)
    return [(np.concatenate(
        [prefix, rng.randint(1, 120, (int(rng.randint(2, 6)),))
         .astype(np.int32)]), int(rng.randint(3, 7))) for _ in range(6)]


def _serve(target, trace):
    reqs = [target.submit(p, m) for p, m in trace]
    target.run()
    return [[int(t) for t in target.output_ids(r)] for r in reqs]


# mechanism -> (constructor, keyword overrides, trace, "the mechanism
# really ran" predicate over the served engine or router). The three
# that only reorder host work over the default engine's own programs
# follow it (_HOST_SIDE below).
_MECHANISMS = {
    "default": (ServeEngine, {}, _mixed_trace,
                lambda e: e.overlap and e.bucket_switches > 0),
    "overlap_off": (ServeEngine, dict(overlap="off"), _mixed_trace,
                    lambda e: not e.overlap and e.bucket_switches > 0),
    "router2": (Router, dict(replicas=2), _mixed_trace,
                lambda r: all(e.decode_steps > 0 for e in r.engines)),
    "policy_slo": (ServeEngine, dict(policy="slo"), _mixed_trace,
                   lambda e: e.policy == "slo"),
    "speculative": (ServeEngine, dict(speculate_k=2, draft=1),
                    _mixed_trace, lambda e: e.stats().spec_windows > 0),
    "prefix_cache": (ServeEngine, dict(prefix_cache=True),
                     _shared_prefix_trace,
                     lambda e: e.stats().prefix_cached_tokens > 0),
    "int8": (ServeEngine, dict(kv_cache_dtype="int8"), _mixed_trace,
             lambda e: e.stats().kv_dtype == "int8"),
    # interpret mode copies the pools at every grid step: a small pool
    # keeps the two kernel compiles at a few seconds
    "pallas": (ServeEngine, dict(kernel="pallas", block_size=8,
                                 num_blocks=12), _mixed_trace,
               lambda e: e.stats().decode_path == "paged_kernel"),
    # under a mesh the dispatch-ahead loop's feed, the previous step's
    # device-resident tokens, is an executable of its own (a committed
    # array's sharding is part of the key; first seen as two 5 s
    # compiles mid-serve on four chips): warm-up compiles it
    "mesh2": (ServeEngine, dict(mesh=2), _mixed_trace,
              lambda e: e.stats().tp == 2 and e.bucket_switches > 0),
    "swap_always": (ServeEngine, dict(swap="always", num_slots=4,
                                      num_blocks=10),
                    lambda: _mixed_trace(1, [(9, 18)] * 5),
                    lambda e: e.stats().swap_ins > 0),
    "roles": (Router, dict(roles={"prefill": 1, "decode": 1}),
              _mixed_trace, lambda r: r.migrations == len(_LENGTHS)),
}


# the same engine as "default", warmed by the same programs: these start
# from whatever the process holds (nothing, when run alone), which saves
# three cold starts of nine compiles each
_HOST_SIDE = ("overlap_off", "router2", "policy_slo")

# a migration extracts and inserts a block set; warm-up compiles those
# two programs only with the host tier on (ROADMAP S12)
_MIGRATION_COMPILES = pytest.mark.xfail(strict=True, reason=(
    "the first migration compiles the block-set extract and insert: "
    "warm-up covers them only under swap != 'off'"))


@pytest.mark.parametrize("mechanism", [
    pytest.param(m, marks=_MIGRATION_COMPILES) if m == "roles" else m
    for m in _MECHANISMS])
def test_no_compile_after_warmup(gpt2_setup, devices8, tmp_path,
                                 mechanism):
    """Every executable a mechanism needs exists after ``warmup()``:
    serving a mixed-length trace across a bucket boundary compiles
    nothing more (a compile inside the loop is a multi-second stall on
    the chip)."""
    _cfg, model, params = gpt2_setup
    build, over, make_trace, engaged = _MECHANISMS[mechanism]
    # the jitted steps are shared by every engine of the process: a
    # case that brings device programs of its own starts cold, so that
    # only its own warm-up can have compiled what its run needs
    if mechanism not in _HOST_SIDE:
        jax.clear_caches()
    obs.reset(out_dir=str(tmp_path / "telemetry"), enabled=True)
    try:
        tracker = obs.compile_tracker()
        target = build(model, params, **{**_GEOM, **over})
        target.warmup()
        count0 = tracker.count
        _serve(target, make_trace())
        assert engaged(target), f"{mechanism} did not run"
        assert tracker.count == count0, \
            f"{mechanism}: compiled after warm-up"
    finally:
        obs.reset()


@pytest.mark.parametrize("scale_out", ["tp2", "router2"])
def test_admission_depth_doubles(gpt2_setup, devices8, scale_out):
    """On the SAME per-device ``kv_pool_bytes`` budget, sharding the
    pools' heads axis over two devices, or a second replica, keeps
    twice the requests resident (uniform block need: prompts pad to one
    chunk, continuations fit the padded span) with identical tokens."""
    cfg, model, params = gpt2_setup
    trace = _mixed_trace(32, [(6, 2)] * 8)
    # 4 blocks of 4 tokens on one device: K and V, float32, every layer
    budget = 4 * 4 * cfg.num_layers * 2 * cfg.hidden_size * 4
    kw = dict(num_slots=6, block_size=4, num_blocks=999, prefill_chunk=8,
              max_model_len=32, kv_pool_bytes=budget)
    base = ServeEngine(model, params, **kw)
    base_outs = _serve(base, trace)
    if scale_out == "tp2":
        wide = ServeEngine(model, params, mesh=2, **kw)
        engines = [wide]
        assert wide.blocks.num_blocks == 9 and base.blocks.num_blocks == 5
        # per-device KV bytes a token: halved by the heads-axis shard
        assert 0 < wide.blocks.token_bytes \
            <= 0.55 * base.blocks.token_bytes
    else:
        wide = Router(model, params, replicas=2, **kw)
        engines = wide.engines
    assert _serve(wide, trace) == base_outs
    assert sum(e.peak_resident for e in engines) \
        >= 2 * base.peak_resident > 0
    # the same budget on every device
    for e in engines:
        assert e.blocks.pool_bytes <= budget + e.blocks.block_bytes


def test_bucketed_gather_reads_less_padding_than_full_width(gpt2_setup):
    """Contexts under the first bucket, served under the ladder and
    under ``gather_buckets="full"``: same tokens, strictly less padded
    read with buckets."""
    _cfg, model, params = gpt2_setup
    trace = _mixed_trace(lengths=[(3, 4), (5, 3), (2, 5), (6, 4)])
    ladder = ServeEngine(model, params, **_GEOM)
    full = ServeEngine(model, params, **dict(_GEOM, gather_buckets="full"))
    assert _serve(ladder, trace) == _serve(full, trace)
    assert 0 <= ladder.stats().gather_waste_mean \
        < full.stats().gather_waste_mean <= 1
    # the report's own ranges
    slo = ladder.slo_summary()
    assert slo["ttft_p99_s"] >= slo["ttft_p50_s"] > 0
    assert 0 < ladder.stats().kv_peak_utilization <= 1


def test_int8_pools_read_at_most_0p6_of_the_fp_bytes_a_step(gpt2_setup):
    """int8 rows plus their fp32 scales are (D + 4) / 4D of the fp32
    rows: the engine's own ``kv_bytes_read`` a decode step says so on a
    uniform trace (same steps, same widths, both pools)."""
    _cfg, model, params = gpt2_setup
    trace = _mixed_trace(4, [(12, 4)] * 6)
    per_step = {}
    for dtype in ("fp", "int8"):
        eng = ServeEngine(model, params, kv_cache_dtype=dtype, **_GEOM)
        _serve(eng, trace)
        st = eng.stats()
        per_step[dtype] = st.kv_bytes_read / st.decode_steps
    assert 0 < per_step["int8"] / per_step["fp"] <= 0.6


def _skip_exact_params(params, keep_layers):
    """Blocks ``>= keep_layers`` write nothing to the residual stream
    (attention and MLP output projections zeroed), so the model equals
    its first ``keep_layers`` blocks and a layer-skip self-draft of that
    depth predicts it exactly, while the target still runs every
    layer."""
    def zero(path, leaf):
        names = [getattr(p, "key", str(p)) for p in path]
        in_tail = any(n.startswith("h_") and int(n[2:]) >= keep_layers
                      for n in names)
        if in_tail and any(n in ("attn_out", "fc_out") for n in names):
            return jnp.zeros_like(leaf)
        return leaf

    return jax.tree_util.tree_map_with_path(zero, params)


def test_skip_exact_draft_fills_the_speculative_window(gpt2_setup):
    _cfg, model, params = gpt2_setup
    params = _skip_exact_params(params, keep_layers=1)
    trace = _mixed_trace(2)
    k = 2
    plain = ServeEngine(model, params, **_GEOM)
    spec = ServeEngine(model, params, speculate_k=k, draft=1, **_GEOM)
    assert _serve(spec, trace) == _serve(plain, trace)
    st = spec.stats()
    assert st.acceptance_rate >= 0.9
    assert 1 <= st.decode_tokens / st.spec_windows <= k + 1
    assert 0 <= st.verify_waste_mean <= 1


def test_slo_policy_misses_fewer_deadlines_than_fifo(gpt2_setup):
    """The whole schedule lands at once on a two-replica fleet, so
    admission ORDER is the only free variable: interactive rows carry a
    tight virtual deadline and priority 0, batch rows a loose one.
    Ordering changes who is admitted when, never what is generated; a
    token bucket on the batch class rejects in the open and everything
    it admits still finishes."""
    cfg, model, params = gpt2_setup
    n_req, tight, rate = 12, 0.008, 100000.0
    rows = make_schedule(
        n_req, 120, process="poisson", rate=rate, seed=13, prompt_lo=4,
        prompt_hi=8, new_lo=3, new_hi=6, eos_token_id=cfg.eos_token_id,
        groups=("interactive", "batch"), priorities=(0, 1),
        deadline_s=(tight, 30.0))

    def serve(policy, rate_limit=None):
        router = Router(model, params, replicas=2, prefix_cache=False,
                        policy=policy, rate_limit=rate_limit, **_GEOM)
        driver = OpenLoopDriver(router, rows, clock="virtual",
                                tick_s=0.001, slo=SloSpec(ttft_s=tight),
                                process="poisson", rate=rate)
        finished = driver.run()
        return ([list(finished[rid].output) for rid in sorted(finished)],
                driver.summary())

    fifo_outs, fifo = serve("fifo")
    slo_outs, slo = serve("slo")
    assert len(fifo_outs) == len(slo_outs) == n_req
    assert slo_outs == fifo_outs
    assert slo["deadline_miss_frac"] < fifo["deadline_miss_frac"]
    limited_outs, limited = serve("slo", {"batch": (1000.0, 2)})
    assert limited["rate_limited"] > 0
    assert len(limited_outs) + limited["rate_limited"] == n_req
