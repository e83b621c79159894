"""The Xing4 configuration written for the benchmark (PR 35): its file
against the catalog's published numbers and ISSUE 35's arithmetic, the
parameter tree it builds, each new reader on a hand-made ``Observed``, the
cell's CPU rehearsal, and the step programs at the published widths
compiled for a described v5e. Every entry of ``BENCHMARK.json`` is found
BY NAME, never by position."""

import importlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import arith_deepseek_v2 as base
from chipbench import arith_xing4 as need
from chipbench import run, spec
from chipbench.device import Observed
from chipbench.families import xing4 as family
from chipbench.reduce import Op, Trace

CELL = "xing4.0-29b-a4b-pp7-gen-sat"
CONFIG = "xing4.0-29b-a4b-pp7"
READERS = ["moe_experts_touched_share", "paged_latent_decode_roofline"]
HBM_BYTES = 15.75 * 2 ** 30   # what the v5e's compiler allows a program


def _file():
    return spec.load_json(os.path.join(spec.HERE, "configs", CONFIG + ".json"))


def _count(tree) -> int:
    return sum(int(np.prod(leaf.shape))
               for leaf in jax.tree_util.tree_leaves(tree))


# -- the cell in the benchmark ---------------------------------------------------

def test_the_cell_is_in_the_benchmark_and_lists_what_it_reports():
    bench = spec.load_benchmark()
    assert spec.check(bench) == []
    config = {c["name"]: c for c in bench["configs"]}[CONFIG]
    assert config["reduced"] == _file()["reduced"] == [
        "num_hidden_layers", "num_nextn_predict_layers"]
    assert config["source"] == _file()["source"]
    cells = {w["name"]: w for w in bench["workloads"]}
    assert cells[CELL] == {
        "name": CELL, "config": CONFIG, "traffic": "gen-sat", "chips": 1,
        "why": cells[CELL]["why"]}
    metrics = {m["name"]: m for m in bench["per_layer"]}
    for name in READERS:
        assert metrics[name]["workloads"] == [CELL]
        assert metrics[name]["moves"] == "serve_out_tok_per_s"
    assert metrics["paged_latent_decode_roofline"]["unit"] == "%"
    cell = spec.load_cell(CELL, False)
    # no itl_p99_ms: in this cell the 99th percentile of a window's gaps
    # sits on the edge of a plateau whose height follows each lap's
    # routing, and spread by half its bound over the builder's runs
    # (PERF.md 6, PR 35); with it go the two per-layer metrics that move it
    assert {m["name"] for m in cell.end_to_end} == {
        "serve_out_tok_per_s", "setup_s"}
    assert all(CELL not in m["workloads"] for m in bench["per_layer"]
               if m["moves"] == "itl_p99_ms")
    # everything else doc-sat reports, its three routed-expert metrics
    # among them, and this cell's own two
    doc = {m["name"] for m in spec.load_cell("deepseek-v2-ep4-doc-sat",
                                            False).per_layer}
    assert {m["name"] for m in cell.per_layer} == (
        doc - {"prefill_dispatch_max_ms", "engine_fetch_wait_max_ms"}
    ) | set(READERS)
    assert {"moe_decode_roofline_share", "moe_prefill_mfu",
            "moe_expert_load_max_over_mean"} <= doc
    for name in cells:
        if name != CELL:
            other = {m["name"] for m in spec.load_cell(name, False).per_layer}
            assert not set(READERS) & other
    # the same lap as Olmo's cell: one traffic file, one engine geometry
    olmo = spec.load_cell("olmo-hybrid-7b-pp2-gen-sat", False)
    assert olmo.traffic == cell.traffic
    for key in ("num_slots", "block_size", "prefill_chunk", "max_model_len"):
        assert olmo.config["deployment"][key] == cell.config["deployment"][key]


# -- the file --------------------------------------------------------------------

def test_file_keeps_every_published_number_but_the_two_cuts():
    row = next(r for r in map(json.loads, open(
        "/opt/skills/guides/model-configs/architectures.jsonl"))
        if r["name"] == "Xing4.0-29B-A4B")
    cfg = _file()
    assert cfg["source"] == row["source_url"]
    cut = {"num_hidden_layers": (6, 40), "num_nextn_predict_layers": (0, 1)}
    for key, value in row["config"].items():
        if key in cut:
            assert (cfg[key], cfg["published"][key], value) == (
                *cut[key], cut[key][1])
        else:
            assert cfg[key] == value, key
    assert (cfg["n_routed_experts"], cfg["vocab_size"], cfg["hc_mult"]) == (
        64, 131072, 4)
    # both leading dense layers and four expert layers; every expert held
    assert base.layer_counts(cfg) == (2, 4)
    assert (cfg["expert_parallel"], cfg["expert_rank"]) == (1, 0)
    dep = cfg["deployment"]
    assert (dep["dtype"], dep["num_slots"], dep["block_size"],
            dep["prefill_chunk"], dep["max_model_len"],
            dep["kv_pool_bytes"]) == ("bfloat16", 64, 16, 512, 4096,
                                      2_000_000_000)
    assert "float32" in dep["dtype_why"]
    assert set(cfg["assumed"]) >= {"map norm and eps", "sinkhorn order",
                                   "streams in and out", "init", "gate"}
    assert 0 < dep["token_margin"] and dep["check_requests"] == 12
    re = cfg["rehearsal"]
    assert (re["num_hidden_layers"], re["first_k_dense_replace"],
            re["n_routed_experts"], re["num_experts_per_tok"],
            re["hc_mult"]) == (3, 1, 8, 2, 4)


def test_arithmetic_is_the_issues():
    """28.41 / 99.09 / 11.01 / 0.344 M an attention, a dense SwiGLU, an
    expert, a sub-layer's ``Phi``; 4,175.8 M parameters; 7,680 B a token;
    the fused kernel's need at 64 slots x 560 keys."""
    cfg = _file()
    assert base.attention_params(cfg) / 1e6 == pytest.approx(28.41, abs=5e-3)
    assert 3 * cfg["hidden_size"] * cfg["intermediate_size"] / 1e6 \
        == pytest.approx(99.09, abs=5e-3)
    assert base.expert_params(cfg) / 1e6 == pytest.approx(11.01, abs=5e-3)
    assert need.map_params(cfg) / 1e6 == pytest.approx(0.344, abs=5e-4)
    assert need.wrap_params(cfg) / 1e6 == pytest.approx(4.13, abs=5e-3)
    assert need.total_params(cfg) / 1e6 == pytest.approx(4175.8, abs=0.1)
    # what licenses the moe_* lists: DeepSeek-V2's arithmetic on THIS file
    # counts every parameter a token passes but the norms and the maps (the
    # embedding is one row a token, and left out on both sides): 0.11% short
    passed = need.total_params(cfg) - cfg["hidden_size"] * cfg["vocab_size"]
    counted = base.fixed_params(cfg) + 4 * 64 * base.expert_params(cfg)
    assert 0 < 1 - counted / passed < 0.002
    assert need.latent_row_bytes(cfg) == 1280
    assert base.latent_token_bytes(cfg) == 6 * 576 * 2      # values alone
    call = need.paged_latent_decode_need(cfg, 64 * 560)
    assert call["bytes"] == 64 * 560 * 1280 == 45_875_200
    assert call["flops"] == 64 * 560 * 2 * 32 * (640 + 512)
    # bound by bytes: 56 us a layer-call against 13 us of the MXU
    assert call["bytes"] / 819e9 == pytest.approx(56.0e-6, rel=1e-2)
    assert call["flops"] / 197e12 < call["bytes"] / 819e9 / 4
    # a decode step at 64 slots and 450 tokens each: 7.4 GB of weights,
    # 5.64 GB of them routed experts
    step = base.decode_step_need(cfg, slots=64, live_tokens=64 * 450,
                                 experts_touched=256, pairs_held=1024)
    assert 256 * base.expert_params(cfg) * 2 / 1e9 == pytest.approx(5.64,
                                                                    abs=5e-3)
    assert step["bytes"] / 1e9 == pytest.approx(7.6, abs=0.1)


def test_the_tree_holds_the_parameters_the_arithmetic_counts():
    """The committed configuration through the program's loader, shapes
    only: 4,175.8 M parameters, part by part."""
    cfg = _file()
    shapes = jax.eval_shape(lambda: family.build(cfg, 0, dtype="bfloat16")[1])
    assert _count(shapes) == need.total_params(cfg)
    assert _count(shapes) / 1e6 == pytest.approx(4175.8, abs=0.1)
    layers = shapes["backbone"]
    assert "layers_5" in layers and "layers_6" not in layers
    assert _count(layers["layers_0"]["self_attn"]) \
        == base.attention_params(cfg) + 768 + 512        # its two norms
    assert _count(layers["layers_1"]["mlp"]) == 3 * 3584 * 9216
    moe = layers["layers_2"]["moe"]
    assert moe["experts_gate_proj"].shape == (64, 3584, 1024)
    assert moe["router"].shape == (3584, 64)
    assert moe["e_score_correction_bias"].shape == (64,)
    assert _count(moe["shared_experts"]) == base.expert_params(cfg)
    for hc in (layers["layers_0"]["attn_hc"], layers["layers_5"]["ffn_hc"]):
        assert hc["phi"].shape == (4 * 3584, 24)
        assert hc["phi"].dtype == jnp.bfloat16
        assert {hc[k].dtype for k in ("alpha", "b_pre", "b_post", "b_res")} \
            == {jnp.dtype(jnp.float32)}
    assert moe["e_score_correction_bias"].dtype == jnp.float32
    assert shapes["lm_head"]["kernel"].shape == (3584, 131072)


# -- the rehearsal ----------------------------------------------------------------

@pytest.mark.parametrize("seed", [11, 2147487801])
def test_the_cell_rehearses_untraced(capsys, seed):
    assert run.main(["--workload", CELL, "--seed", str(seed), "--seconds",
                     "2", "--trace", "0"]) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert set(line["metrics"]) == {"serve_out_tok_per_s", "setup_s"}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["device"]["count"] == 1


def test_the_cell_rehearses_traced_with_its_own_metrics(capsys):
    assert run.main(["--workload", CELL, "--seed", "5", "--seconds", "3",
                     "--trace", "1"]) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True
    assert line["metrics"]["compiles_in_window"]["value"] == 0
    # counts read on a CPU too; the shares of a peak and the kernel's
    # roofline read nothing there (no peak, a gather step)
    assert 0 < line["metrics"]["moe_experts_touched_share"]["value"] <= 100
    assert line["metrics"]["moe_expert_load_max_over_mean"]["value"] >= 1
    assert not {"paged_latent_decode_roofline", "moe_decode_roofline_share",
                "hbm_live_share"} & set(line["metrics"])
    assert 0 < line["device"]["busy_s"] <= line["device"]["window_s"]


# -- the readers ------------------------------------------------------------------

def _line(i, **kw):
    return dict(type="serve", event="iteration_ledger", iteration=i,
                fetch_wait_s=0.0, dur_s=0.01, **kw)


@pytest.fixture()
def read():
    """``read(name, events, ops)``: the reader ``name`` on a hand-made run
    of the cell with these ledger lines and these traced operations
    ``(name, start, end)`` (None: no trace)."""
    cell = spec.load_cell(CELL, False)

    def read(name, events, ops, live=28_800.0, traced=(1, 3)):
        trace = Trace(ops=[Op(0, n, a, b) for n, a, b in ops], modules=[],
                      annotations=[]) if ops is not None else None
        return importlib.import_module(
            "chipbench.layers." + name).read(Observed(
            cell=cell, device_kind="TPU v5 lite", chips=1, window_s=51.0,
            values={}, counters={"kv_live_tokens_mean": live, "laps": {
                "traced": {"lap": 12, "iterations": list(traced)}}},
            events=events, trace=trace, trace_window_s=1.0,
            memory_peak_bytes=0, memory_limit_bytes=0, compiles_in_window=0))

    return read


DECODE = dict(moe_pairs=1024, moe_pairs_held=1024, moe_decode_pairs=1024,
              moe_decode_pairs_held=1024, moe_experts_touched=[63, 62, 64, 63],
              moe_expert_load_max=[9, 10, 8, 9],
              moe_expert_load_mean=[4.0] * 4, mhc_defect_max=1e-6,
              prefill_chunks=0, prefill_keys_needed=0, decode_slots=64)


def test_moe_experts_touched_share_on_a_hand_made_run(read):
    name = "moe_experts_touched_share"
    # iteration 0 lies outside the traced range
    events = [_line(0, **dict(DECODE, moe_experts_touched=[10] * 4))] + [
        _line(i, **DECODE) for i in (1, 2)] + [
        _line(3, **dict(DECODE, moe_experts_touched=[64] * 4))]
    got = read(name, events, None)
    assert got == pytest.approx(100 * (2 * 252 + 256) / (3 * 256))
    assert 98 < got < 100
    # nothing to read: a program whose ledger has no routed counts
    assert read(name, [_line(i, decode_slots=64) for i in (1, 2)], None) is None
    assert read(name, [], None) is None


def test_paged_latent_decode_roofline_on_a_hand_made_run(read):
    name = "paged_latent_decode_roofline"
    # six layer-calls a step, two steps, 120 us a call
    ops = [("paged_latent_decode.%d" % i, 0.001 * i, 0.001 * i + 120e-6)
           for i in range(12)] + [("fusion.7", 0.5, 0.6)]
    got = read(name, [], ops)
    call = need.paged_latent_decode_need(_file(), 28_800)
    assert call["bytes"] / 819e9 > call["flops"] / 197e12     # memory-bound
    assert got == pytest.approx(100 * (call["bytes"] / 819e9) / 120e-6)
    assert 30 < got < 45
    # nothing to read: no trace, a trace without the kernel (a gather
    # step, the parent of PR 34), no count of resident tokens
    assert read(name, [], None) is None
    assert read(name, [], [("fusion.7", 0.5, 0.6)]) is None
    assert read(name, [], ops, live=None) is None


# -- the step programs at the published widths, compiled for the v5e --------------

@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever keeps libtpu away
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def published(topo):
    from jax.sharding import SingleDeviceSharding

    from huggingface_sagemaker_tensorflow_distributed_tpu.models.xing4 import (
        Xing4ForCausalLM,
        xing4_config_from_hf,
    )
    from huggingface_sagemaker_tensorflow_distributed_tpu.serve import engine

    one_chip = SingleDeviceSharding(topo.devices[0])
    cfg = _file()
    dep = cfg["deployment"]
    model = Xing4ForCausalLM(xing4_config_from_hf(
        family.program_config(cfg), dtype=jnp.bfloat16,
        param_dtype=jnp.bfloat16))
    dummy = jnp.ones((1, 8), jnp.int32)
    pshape = jax.eval_shape(
        lambda k: model.init(k, dummy, dummy)["params"], jax.random.PRNGKey(0))
    plan, pool_shapes = engine.build_cache_plan(model, pshape,
                                                dep["max_model_len"])
    token_bytes = sum(h * d * np.dtype(t).itemsize for h, d, t in pool_shapes)
    blocks = 1 + dep["kv_pool_bytes"] // (dep["block_size"] * token_bytes)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def rows(n):
        nb = dep["max_model_len"] // dep["block_size"]
        return (sds((n, nb), jnp.int32), sds((n,), jnp.int32))

    def sampling(n):
        return (sds((n,), jnp.float32), sds((n,), jnp.int32),
                sds((n,), jnp.float32), sds((n, 2), jnp.uint32),
                sds((n,), jnp.int32))

    return dict(
        engine=engine, model=model, dep=dep, plan=plan, sds=sds, rows=rows,
        sampling=sampling, token_bytes=token_bytes,
        params=jax.tree_util.tree_map(lambda l: sds(l.shape, l.dtype), pshape),
        pools=[sds(shape, t) for shape, (_h, _d, t) in zip(
            engine.pool_dims(plan, pool_shapes, blocks, dep["block_size"]),
            pool_shapes)])


@pytest.fixture()
def on_the_chip(topo, monkeypatch):
    """The kernels' wrappers ask ``jax.devices()`` whether to interpret
    them, and the model asks the default backend how a chunk attends:
    both steered here, in the test, to what a TPU gives."""
    from huggingface_sagemaker_tensorflow_distributed_tpu.models import (
        deepseek_v2,
    )

    monkeypatch.setattr(jax, "devices", lambda *a, **k: [topo.devices[0]])
    monkeypatch.setattr(
        deepseek_v2, "_seen_form",
        lambda cfg, q_len, width: deepseek_v2.expanded_form(
            cfg, q_len, width, platform="tpu", mesh=False))


def _total_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)


def test_sizes_are_the_issues(published):
    q = published
    assert q["token_bytes"] == 7_680            # 576 values in 640 lanes x 6
    assert [k[0] for k in q["plan"].kinds].count("latent") == 6
    assert not q["plan"].state_shapes
    assert [p.shape[1:] for p in q["pools"]] == [(16, 640)] * 6
    # 260 k tokens: 64 slots x 3,072 and nearly a third spare
    assert q["pools"][0].shape[0] * 16 == 260_432 > 1.32 * 64 * 3072


def test_paged_decode_step_fits_with_the_fused_kernel(published, on_the_chip):
    """The decode step as the engine runs it on a TPU at the 4,096 bucket:
    the fused paged latent kernel at 32 heads (its blocks assume no head
    count), the grouped matmuls over 64 experts, twelve wraps; weights and
    pools 10.4 GB, no pool copied."""
    q, n = published, published["dep"]["num_slots"]
    tables, ctx = q["rows"](n)
    step = jax.jit(
        lambda p, pools, *a: q["engine"]._paged_decode_step(
            q["model"], p, pools, *a, q["plan"], 4096, False),
        donate_argnums=(1,))
    compiled = step.lower(q["params"], q["pools"], q["sds"]((n,), jnp.int32),
                          tables, ctx, q["sds"]((n,), jnp.bool_),
                          *q["sampling"](n)).compile()
    assert _total_bytes(compiled) <= HBM_BYTES
    text = compiled.as_text()
    assert "copy(%pools_" not in text
    assert "paged_latent_decode" in text


def test_four_row_prefill_fits_with_the_fused_kernel(published, on_the_chip):
    q, g, c = published, 4, published["dep"]["prefill_chunk"]
    tables, start = q["rows"](g)
    step = jax.jit(
        lambda p, pools, *a: q["engine"]._prefill_chunk(
            q["model"], p, pools, *a, q["plan"], False, 1024),
        donate_argnums=(1,))
    compiled = step.lower(q["params"], q["pools"], q["sds"]((g, c), jnp.int32),
                          tables, start, q["sds"]((g,), jnp.int32),
                          *q["sampling"](g)).compile()
    assert _total_bytes(compiled) <= HBM_BYTES
    text = compiled.as_text()
    assert "latent_prefill" in text
    # the head runs on one row a chunk row, not on 4 x 512
    assert "f32[4,512,131072]" not in text
