"""The Olmo-Hybrid configuration written for the benchmark (PR 33): its
file against the catalog's published numbers and ISSUE 33's arithmetic,
its plain reference against the program at rehearsal size, each new reader
on a hand-made ``Observed``, the cell's CPU rehearsal, and the step
programs at the published widths compiled for a described v5e."""

import importlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import arith_olmo_hybrid as need
from chipbench import run, spec
from chipbench.device import Observed
from chipbench.families import olmo_hybrid as family
from chipbench.reduce import Op, Trace
from chipbench.reference import olmo_hybrid as reference

CELL = "olmo-hybrid-7b-pp2-gen-sat"
READERS = ["hybrid_decode_roofline_share", "hybrid_prefill_mfu",
           "state_hbm_share"]
HBM_BYTES = 15.75 * 2 ** 30   # what the v5e's compiler allows a program


def _file():
    return spec.load_json(os.path.join(spec.HERE, "configs",
                                       "olmo-hybrid-7b-pp2.json"))


# -- the cell in the benchmark ---------------------------------------------------

def test_the_cell_is_in_the_benchmark_and_lists_what_it_reports():
    """Found by NAME, wherever the entries stand: the next addition is
    appended behind them (``test_deepseek_v2_cell.py`` asserts that ITS
    entries are the last ones, and has failed on that since this cell was
    appended: a `benchmark` PR's repair, PERF.md 4)."""
    bench = spec.load_benchmark()
    assert spec.check(bench) == []
    config = {c["name"]: c for c in bench["configs"]}["olmo-hybrid-7b-pp2"]
    assert config["reduced"] == _file()["reduced"] == [
        "num_hidden_layers", "layer_types"]
    cells = {w["name"]: w for w in bench["workloads"]}
    assert cells[CELL] == {
        "name": CELL, "config": "olmo-hybrid-7b-pp2", "traffic": "gen-sat",
        "chips": 1, "why": cells[CELL]["why"]}
    metrics = {m["name"]: m for m in bench["per_layer"]}
    for name in READERS:
        assert metrics[name]["workloads"] == [CELL]
        assert metrics[name]["moves"] == "serve_out_tok_per_s"
    cell = spec.load_cell(CELL, False)
    assert {m["name"] for m in cell.end_to_end} == {
        "serve_out_tok_per_s", "itl_p99_ms", "setup_s"}
    got = {m["name"] for m in cell.per_layer}
    # everything doc-sat reports but its three routed-expert metrics
    doc = {m["name"] for m in spec.load_cell("deepseek-v2-ep4-doc-sat",
                                            False).per_layer}
    assert got == (doc - {"moe_decode_roofline_share", "moe_prefill_mfu",
                          "moe_expert_load_max_over_mean"}) | set(READERS)
    # its token_bytes knows K/V only: not this cell's roofline
    assert "decode_hbm_roofline_share" not in got
    for name in cells:
        if name != CELL:
            other = {m["name"] for m in spec.load_cell(name, False).per_layer}
            assert not set(READERS) & other


# -- the file --------------------------------------------------------------------

def test_file_keeps_every_published_number_but_the_depth():
    row = next(r for r in map(json.loads, open(
        "/opt/skills/guides/model-configs/architectures.jsonl"))
        if r["name"] == "Olmo-Hybrid-7B")
    cfg = _file()
    assert cfg["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key == "num_hidden_layers":
            assert (cfg[key], cfg["published"][key]) == (16, value == 32 and 32)
        elif key == "layer_types":
            assert cfg[key] == value[:16] and len(value) == 32
        else:
            assert cfg[key] == value, key
    # four whole periods of the published pattern
    assert cfg["layer_types"] == (["linear_attention"] * 3
                                  + ["full_attention"]) * 4
    dep = cfg["deployment"]
    assert (dep["dtype"], dep["state_dtype"]) == ("bfloat16", "float32")
    assert (dep["num_slots"], dep["block_size"], dep["prefill_chunk"],
            dep["max_model_len"], dep["kv_pool_bytes"]) == (
        64, 16, 512, 4096, 3_500_000_000)
    assert set(cfg["assumed"]) >= {"block", "rope", "init"}


def test_arithmetic_is_the_issues():
    """88.75 / 58.99 / 126.81 M a linear mixer, a full attention, an MLP;
    4,101 M parameters; 27.37 MB of state a slot, 61,440 B a token; a
    decode step at 64 slots and 500 tokens each needs 12.79 GB without the
    convolution tails and 12.90 GB with them (7.43 of weights + 3.40 +
    0.11 of state + 1.97 of K/V)."""
    cfg = _file()
    assert need.layer_counts(cfg) == (12, 4)
    assert need.linear_mixer_params(cfg) / 1e6 == pytest.approx(88.75, abs=5e-3)
    assert need.full_mixer_params(cfg) / 1e6 == pytest.approx(58.99, abs=5e-3)
    assert need.mlp_params(cfg) / 1e6 == pytest.approx(126.81, abs=5e-3)
    assert round(need.total_params(cfg) / 1e6) == 4101
    assert need.kv_token_bytes(cfg) == 61_440
    assert need.recurrent_state_bytes(cfg) == 12 * 2_211_840
    assert need.conv_tail_bytes(cfg) == 12 * 69_120
    assert need.state_bytes_per_slot(cfg) == 27_371_520
    step = need.decode_step_need_bytes(cfg, slots=64, kv_tokens=64 * 500)
    assert step["weights"] / 1e9 == pytest.approx(7.43, abs=5e-3)
    assert step["state"] / 1e9 == pytest.approx(3.40, abs=5e-3)
    assert step["conv_tails"] / 1e9 == pytest.approx(0.11, abs=5e-3)
    assert step["kv"] / 1e9 == pytest.approx(1.97, abs=5e-3)
    assert step["total"] / 1e9 == pytest.approx(12.90, abs=5e-3)
    assert need.decode_step_need_bytes(
        cfg, slots=64, kv_tokens=64 * 500, conv_tails=False)[
        "total"] / 1e9 == pytest.approx(12.79, abs=5e-3)
    # a four-row dispatch of whole chunks at start 0: ISSUE 33's 13.6 TFLOP
    # without the recurrence and the attention
    assert need.prefill_need_flops(cfg, 2048, 4, 2048, 512) / 1e12 \
        == pytest.approx(13.8, abs=0.1)


def test_the_tree_holds_the_parameters_the_arithmetic_counts():
    """The committed configuration through the program's loader, shapes
    only: 4,101 M parameters, mixer by mixer."""
    cfg = _file()
    shapes = jax.eval_shape(lambda: family.build(cfg, 0, dtype="bfloat16")[1])

    def count(tree):
        return sum(int(np.prod(l.shape))
                   for l in jax.tree_util.tree_leaves(tree))

    layers = shapes["backbone"]
    assert count(shapes) == need.total_params(cfg)
    assert round(count(shapes) / 1e6) == 4101
    assert count(layers["layers_0"]["linear_attn"]) \
        == need.linear_mixer_params(cfg)
    assert count(layers["layers_3"]["self_attn"]) \
        == need.full_mixer_params(cfg)
    assert count(layers["layers_0"]["mlp"]) == need.mlp_params(cfg)
    assert "linear_attn" in layers["layers_14"]
    assert "self_attn" in layers["layers_15"] and "layers_16" not in layers
    a = layers["layers_0"]["linear_attn"]
    assert a["A_log"].dtype == a["dt_bias"].dtype == jnp.float32
    assert a["q_proj"]["kernel"].dtype == jnp.bfloat16


# -- reference against program, and the rehearsal -------------------------------

@pytest.fixture(scope="module")
def rehearsal():
    cfg = spec.merge(_file(), _file()["rehearsal"])
    model, params = family.build(cfg, 7, dtype="float32")
    return cfg, model, params


@pytest.mark.parametrize("length", [40, 1024])
def test_reference_is_the_program_at_rehearsal_size(rehearsal, length):
    cfg, model, params = rehearsal
    assert cfg["num_hidden_layers"] == 4               # one period
    tokens = jnp.asarray(np.random.default_rng(length).integers(
        3, cfg["vocab_size"], size=length, dtype=np.int32))
    got = model.apply({"params": params}, tokens[None])[0]
    pad = -length % 512 if length > 512 else 0
    want = reference.logits(params, cfg, jnp.pad(tokens, (0, pad)),
                            jnp.arange(length))
    assert float(jnp.abs(got).max()) > 0.1
    np.testing.assert_allclose(got, want, atol=2e-5)


@pytest.mark.parametrize("seed", [11, 2147487801])
def test_the_cell_rehearses_untraced(capsys, seed):
    assert run.main(["--workload", CELL, "--seed", str(seed), "--seconds",
                     "2", "--trace", "0"]) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert set(line["metrics"]) == {"serve_out_tok_per_s", "itl_p99_ms",
                                    "setup_s"}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["device"]["count"] == 1


def test_the_cell_rehearses_traced(capsys):
    assert run.main(["--workload", CELL, "--seed", "5", "--seconds", "3",
                     "--trace", "1"]) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True
    assert line["metrics"]["compiles_in_window"]["value"] == 0
    # the CPU has no peak and no memory limit: the three new shares read
    # nothing there; the accepted counters do
    assert line["metrics"]["prefix_hit_share"]["value"] == 0
    assert {"prefill_width_fill_share", "decode_batch_occupancy",
            "engine_host_ms_per_step"} <= set(line["metrics"])
    assert 0 < line["device"]["busy_s"] <= line["device"]["window_s"]


# -- the readers -----------------------------------------------------------------

def _line(i, **kw):
    return dict(type="serve", event="iteration_ledger", iteration=i,
                fetch_wait_s=0.0, dur_s=0.01, **kw)


@pytest.fixture()
def read():
    """``read(name, events, modules)``: the reader ``name`` on a
    hand-made run of the cell with these ledger lines and these traced
    modules (None: no trace)."""
    cell = spec.load_cell(CELL, False)

    def read(name, events, modules, traced=(1, 3), limit=16_900_000_000):
        trace = Trace(ops=[], modules=[Op(0, n, a, b) for n, a, b in modules],
                      annotations=[]) if modules is not None else None
        return importlib.import_module(
            "chipbench.layers." + name).read(Observed(
            cell=cell, device_kind="TPU v5 lite", chips=1, window_s=51.0,
            values={}, counters={"laps": {
                "traced": {"lap": 5, "iterations": list(traced)}}},
            events=events, trace=trace, trace_window_s=1.0,
            memory_peak_bytes=0, memory_limit_bytes=limit,
            compiles_in_window=0))

    return read


DECODE = dict(state_slots=64, kv_tokens_resident=32_000, prefill_chunks=0,
              prefill_tokens=0, prefill_keys_needed=0, state_slots_peak=64,
              decode_slots=64)


def test_hybrid_decode_roofline_share_on_a_hand_made_run(read):
    name = "hybrid_decode_roofline_share"
    # iteration 0 lies outside the traced range; iteration 2 also ran a
    # prefill row, which is no slot of its decode step
    events = [_line(0, **dict(DECODE, state_slots=10))] + [
        _line(1, **DECODE),
        _line(2, **dict(DECODE, state_slots=65, prefill_chunks=1,
                        prefill_tokens=300, prefill_keys_needed=512)),
        _line(3, **DECODE)]
    modules = [("jit__paged_decode_step(1)", 0.0, 0.020),
               ("jit__paged_decode_step(1)", 0.030, 0.050)]
    got = read(name, events, modules)
    want = need.decode_step_need_bytes(_file(), slots=64,
                                       kv_tokens=32_000)["total"]
    assert want / 1e9 == pytest.approx(12.90, abs=5e-3)
    assert got == pytest.approx(100 * want / 819e9 / 0.020)
    assert 0 < got < 100
    # nothing to read: a program without the counts, or no trace
    bare = [_line(i, decode_slots=64) for i in (1, 2, 3)]
    assert read(name, bare, modules) is None
    assert read(name, events, None) is None
    assert read(name, events, []) is None


def test_hybrid_prefill_mfu_counts_real_tokens_only(read):
    name = "hybrid_prefill_mfu"
    pre = dict(DECODE, state_slots=68, prefill_chunks=4, prefill_tokens=1500,
               prefill_keys_needed=4 * 512)
    events = [_line(1, **pre), _line(2, **DECODE)]
    modules = [("jit__prefill_chunk(2)", 0.0, 0.120),
               ("jit__paged_decode_step(1)", 0.120, 0.140)]
    got = read(name, events, modules)
    flops = need.prefill_need_flops(_file(), tokens=1500, rows=4,
                                    keys_needed=2048, chunk=512)
    assert got == pytest.approx(100 * flops / 0.120 / 197e12)
    assert 0 < got < 100
    assert read(name, [_line(1, decode_slots=3, prefill_chunks=4)],
                modules) is None
    assert read(name, events, None) is None


def test_state_hbm_share_is_the_peak_slots_state(read):
    name = "state_hbm_share"
    events = [_line(0, **dict(DECODE, state_slots_peak=40)),
              _line(1, **DECODE), _line(2, **DECODE)]
    got = read(name, events, None)
    assert got == pytest.approx(100 * 64 * 27_371_520 / 16.9e9)
    assert got == pytest.approx(10.4, abs=0.05)
    assert read(name, [_line(1, decode_slots=64)], None) is None
    assert read(name, events, None, limit=0) is None


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

    from huggingface_sagemaker_tensorflow_distributed_tpu.models.olmo_hybrid import (
        OlmoHybridForCausalLM,
        olmo_hybrid_config_from_hf,
    )
    from huggingface_sagemaker_tensorflow_distributed_tpu.serve import engine

    one_chip = SingleDeviceSharding(topo.devices[0])
    cfg = _file()
    dep = cfg["deployment"]
    model = OlmoHybridForCausalLM(olmo_hybrid_config_from_hf(
        cfg, dtype=jnp.bfloat16, param_dtype=jnp.bfloat16))
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
            pool_shapes)],
        states=[sds((dep["num_slots"],) + shape, jnp.dtype(t))
                for shape, t in plan.state_shapes])


def _total_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)


def _assert_nothing_big_is_copied(text: str) -> None:
    """K/V pools (437 MB each) and recurrent-state pools (144 MB each) are
    written where they lie. (The 4.5 MB convolution tails may move.)"""
    import re

    assert "copy(%pools_" not in text
    assert not re.search(r"f32\[64,30,96,192\]\S* (copy|slice)\(", text)


def test_sizes_are_the_issues(published):
    q = published
    assert q["token_bytes"] == 61_440
    assert [k[0] for k in q["plan"].kinds].count("state") == 24
    per_slot = sum(int(np.prod(s.shape[1:])) * s.dtype.itemsize
                   for s in q["states"])
    assert per_slot == 27_371_520
    assert [p.shape[1:] for p in q["pools"]] == [(16, 30, 128)] * 8


@pytest.mark.parametrize("bucket", [1024, 4096])
def test_paged_decode_step_fits_at_both_buckets(published, topo, bucket,
                                                monkeypatch):
    """The decode step as the engine runs it on a TPU (the fused paged
    kernel for the four full layers, the recurrence's one-token step for
    the twelve linear ones) with weights, K/V pools and state pools. A
    K/V pool of 30 heads lies head-major on the chip: kernel and scatter
    both address it that way (``head_major_rows``), or every pool is
    copied whole twice a step."""
    # the kernel's wrapper asks jax.devices() whether to interpret it:
    # steer it here, in the test, to lower for the TPU
    monkeypatch.setattr(jax, "devices", lambda *a, **k: [topo.devices[0]])
    q, n = published, published["dep"]["num_slots"]
    tables, ctx = q["rows"](n)
    step = jax.jit(
        lambda p, pools, states, *a: q["engine"]._paged_decode_step(
            q["model"], p, pools, *a, q["plan"], bucket, False, states),
        donate_argnums=(1, 2))
    compiled = step.lower(q["params"], q["pools"], q["states"],
                          q["sds"]((n,), jnp.int32), tables, ctx,
                          q["sds"]((n,), jnp.bool_),
                          *q["sampling"](n)).compile()
    assert _total_bytes(compiled) <= HBM_BYTES
    text = compiled.as_text()
    _assert_nothing_big_is_copied(text)
    assert "tpu_custom_call" in text              # the fused paged kernel


@pytest.mark.parametrize("bucket", [1024, 4096])
def test_four_row_prefill_fits_at_both_buckets(published, bucket):
    q, g, c = published, 4, published["dep"]["prefill_chunk"]
    tables, start = q["rows"](g)
    step = jax.jit(
        lambda p, pools, states, rows, *a: q["engine"]._prefill_chunk(
            q["model"], p, pools, *a, q["plan"], False, bucket, states, rows),
        donate_argnums=(1, 2))
    compiled = step.lower(q["params"], q["pools"], q["states"],
                          q["sds"]((g,), jnp.int32),
                          q["sds"]((g, c), jnp.int32), tables, start,
                          q["sds"]((g,), jnp.int32),
                          *q["sampling"](g)).compile()
    text = compiled.as_text()
    _assert_nothing_big_is_copied(text)
    # the head runs on one row a chunk row, not on 4 x 512
    assert "f32[4,512,100352]" not in text
    if bucket == 1024:      # every dispatch of the cell's traffic
        assert _total_bytes(compiled) <= HBM_BYTES
