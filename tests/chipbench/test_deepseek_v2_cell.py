"""The DeepSeek-V2 configuration written for the benchmark (PR 28): its
plain reference against the program at rehearsal size, its file against
the published widths, each new reader on a hand-made ``Observed``, and
the step programs at the published widths compiled for a described v5e.

The cell is in ``BENCHMARK.json``; ``test_cells.py`` rehearses it with the
others, and here it runs on two more seeds and with its own metrics."""

import importlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import arith_deepseek_v2 as need
from chipbench import run, spec
from chipbench.device import Observed
from chipbench.families import deepseek_v2 as family
from chipbench.reduce import Op, Trace
from chipbench.reference import deepseek_v2 as reference

CELL = "deepseek-v2-ep4-doc-sat"
READERS = ["moe_decode_roofline_share", "moe_prefill_mfu",
           "moe_expert_load_max_over_mean"]
HBM_BYTES = 15.75 * 2 ** 30   # what the v5e's compiler allows a program


def _file():
    return spec.load_json(os.path.join(spec.HERE, "configs",
                                       "deepseek-v2-ep4.json"))


# -- the cell in the benchmark ---------------------------------------------------

def test_the_cell_is_appended_and_lists_what_it_reports():
    # every entry found BY NAME: later cells are appended behind these
    bench = spec.load_benchmark()
    assert spec.check(bench) == []
    config = {c["name"]: c for c in bench["configs"]}["deepseek-v2-ep4"]
    assert config["reduced"] == _file()["reduced"]
    cells = {w["name"]: w for w in bench["workloads"]}
    assert cells[CELL] == {
        "name": CELL, "config": "deepseek-v2-ep4", "traffic": "doc-sat",
        "chips": 1, "why": cells[CELL]["why"]}
    metrics = {m["name"]: m for m in bench["per_layer"]}
    for name in READERS:
        assert CELL in metrics[name]["workloads"]
    cell = spec.load_cell(CELL, False)
    assert {m["name"] for m in cell.end_to_end} == {
        "serve_out_tok_per_s", "itl_p99_ms", "setup_s"}
    got = {m["name"] for m in cell.per_layer}
    assert set(READERS) | {"compiles_in_window", "hbm_live_share",
                           "engine_warmup_s"} <= got
    # its bytes are every weight once: not this cell's roofline
    assert "decode_hbm_roofline_share" not in got
    # and no cell that does not route experts reports this cell's metrics
    routed = {CELL, "xing4.0-29b-a4b-pp7-gen-sat"}
    for name in set(cells) - routed:
        other = {m["name"] for m in spec.load_cell(name, False).per_layer}
        assert not set(READERS) & other


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


def test_the_cell_rehearses_traced_with_its_own_metrics(capsys):
    assert run.main(["--workload", CELL, "--seed", "5", "--seconds", "3",
                     "--trace", "1"]) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True
    assert line["metrics"]["compiles_in_window"]["value"] == 0
    # the CPU has no peak: the two shares of a roofline read nothing
    assert line["metrics"]["moe_expert_load_max_over_mean"]["value"] >= 1
    assert {"prefill_width_fill_share", "decode_batch_occupancy",
            "engine_host_ms_per_step"} <= set(line["metrics"])
    assert 0 < line["device"]["busy_s"] <= line["device"]["window_s"]


# -- reference against program -------------------------------------------------

@pytest.fixture(scope="module")
def rehearsal():
    cfg = spec.merge(_file(), _file()["rehearsal"])
    model, params = family.build(cfg, 7, dtype="float32")
    return cfg, model, params


@pytest.mark.parametrize("length", [40, 1024])
def test_reference_is_the_program_at_rehearsal_size(rehearsal, length):
    """Float32 on both sides on the CPU, where a matmul is exact to
    rounding: logits of order 1 agree to a few float32 ulps of the
    hidden state's sums (measured 3e-7 at 40 tokens); 2e-5 leaves room
    for the 1,024-token softmax and no room for a wrong frequency, scale,
    gate or share (each moves a logit by 1e-2 or more)."""
    cfg, model, params = rehearsal
    tokens = jnp.asarray(np.random.default_rng(length).integers(
        3, cfg["vocab_size"], size=length, dtype=np.int32))
    got = model.apply({"params": params}, tokens[None])[0]
    pad = -length % 512 if length > 512 else 0
    want = reference.logits(params, cfg, jnp.pad(tokens, (0, pad)),
                            jnp.arange(length))
    assert float(jnp.abs(got).max()) > 0.1
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_reference_refuses_a_router_of_another_width(rehearsal):
    cfg, model, params = rehearsal
    with pytest.raises(ValueError, match="router"):
        reference.logits(params, dict(cfg, expert_parallel=4),
                         jnp.ones((8,), jnp.int32), jnp.arange(8))


def test_reference_reads_nothing_of_the_programs_models():
    src = open(reference.__file__).read()
    assert "huggingface_sagemaker" not in src and "models" not in src.split(
        '"""', 2)[2]


# -- the file --------------------------------------------------------------------

def test_file_keeps_every_published_number_but_the_three_cuts():
    import json

    row = next(r for r in map(json.loads, open(
        "/opt/skills/guides/model-configs/architectures.jsonl"))
        if r["name"] == "DeepSeek-V2") if os.path.exists(
        "/opt/skills/guides/model-configs/architectures.jsonl") else None
    cfg = _file()
    assert cfg["reduced"] == ["num_hidden_layers", "n_routed_experts",
                              "vocab_size"]
    widths = dict(hidden_size=5120, num_attention_heads=128,
                  q_lora_rank=1536, kv_lora_rank=512, qk_rope_head_dim=64,
                  qk_nope_head_dim=128, v_head_dim=128,
                  moe_intermediate_size=1536, intermediate_size=12288,
                  n_group=8, topk_group=3, num_experts_per_tok=6,
                  routed_scaling_factor=16, n_shared_experts=2)
    assert {k: cfg[k] for k in widths} == widths
    assert cfg["n_routed_experts"] * cfg["expert_parallel"] == 160
    assert cfg["published"]["n_routed_experts"] == 160
    assert (cfg["num_hidden_layers"], cfg["vocab_size"]) == (5, 25600)
    if row is not None:
        changed = {k for k, v in row["config"].items() if cfg.get(k) != v}
        assert changed == set(cfg["reduced"])
        assert cfg["source"] == row["source_url"]
    # the floors of a model_config cut: a dense layer + four expert
    # layers, at least 8 experts, at least an eighth of the vocabulary
    assert cfg["num_hidden_layers"] - cfg["first_k_dense_replace"] >= 4
    assert cfg["n_routed_experts"] >= 8 and cfg["vocab_size"] * 8 >= 102400


def test_arithmetic_is_the_issues_table():
    cfg = _file()
    assert need.attention_params(cfg) == pytest.approx(149.2e6, rel=1e-3)
    assert need.expert_params(cfg) == 23_592_960
    held = (need.fixed_params(cfg) + cfg["hidden_size"] * cfg["vocab_size"]
            + 4 * cfg["n_routed_experts"] * need.expert_params(cfg))
    assert held == pytest.approx(5.164e9, rel=1e-3)     # 10.33 GB in bf16
    assert need.latent_token_bytes(cfg) == 5760         # 6,400 as stored
    step = need.decode_step_need(cfg, slots=32, live_tokens=0,
                                 experts_touched=160, pairs_held=192)
    assert step["bytes"] == pytest.approx(10.07e9, rel=1e-2)  # all but embed


def test_the_program_builds_the_share_the_file_states():
    model, _ = None, None
    from huggingface_sagemaker_tensorflow_distributed_tpu.models.deepseek_v2 import (
        deepseek_v2_config_from_hf,
    )

    cfg = deepseek_v2_config_from_hf(family.program_config(_file()))
    assert (cfg.n_routed_experts, cfg.held, cfg.expert_rank) == (160, 40, 0)
    assert cfg.num_layers == 5 and cfg.num_moe_layers == 4
    assert cfg.softmax_scale == pytest.approx(0.11472, abs=1e-5)


# -- the readers -----------------------------------------------------------------

def _line(it, **kw):
    return dict(type="serve", event="iteration_ledger", iteration=it,
                fetch_wait_s=0.0, dur_s=0.01, **kw)


@pytest.fixture()
def read():
    """``read(name, events, modules)``: the reader ``name`` on a
    hand-made run of the cell with these ledger lines and these traced
    modules (None: no trace)."""
    cell = spec.load_cell(CELL, False)

    def read(name, events, modules, live=90_000.0, traced=(1, 3)):
        trace = Trace(ops=[], modules=[Op(0, n, a, b) for n, a, b in modules],
                      annotations=[]) if modules is not None else None
        return importlib.import_module(
            "chipbench.layers." + name).read(Observed(
            cell=cell, device_kind="TPU v5 lite", chips=1, window_s=51.0,
            values={}, counters={"kv_live_tokens_mean": live, "laps": {
                "traced": {"lap": 5, "iterations": list(traced)}}},
            events=events, trace=trace, trace_window_s=1.0,
            memory_peak_bytes=0, memory_limit_bytes=0, compiles_in_window=0))

    return read


DECODE = dict(moe_pairs=768, moe_pairs_held=192, moe_decode_pairs=768,
              moe_decode_pairs_held=192, moe_experts_touched=[28] * 4,
              moe_expert_load_max=[4, 3, 5, 4],
              moe_expert_load_mean=[1.2] * 4, prefill_chunks=0,
              prefill_keys_needed=0, decode_slots=32)


def test_moe_decode_roofline_share_on_a_hand_made_run(read):
    name = "moe_decode_roofline_share"
    events = [_line(0, **dict(DECODE, moe_experts_touched=[40] * 4))] + [
        _line(i, **DECODE) for i in (1, 2, 3)]
    modules = [("jit__decode_step(1)", 0.0, 0.020),
               ("jit__decode_step(1)", 0.030, 0.050)]
    got = read(name, events, modules)
    cfg = _file()
    want = need.decode_step_need(cfg, slots=32, live_tokens=90_000,
                                 experts_touched=112, pairs_held=192)
    assert want["bytes"] / 819e9 > want["flops"] / 197e12     # memory-bound
    assert got == pytest.approx(100 * want["bytes"] / 819e9 / 0.020)
    assert 0 < got < 100
    # nothing to read: a program without the counts, or no trace
    bare = [_line(i, decode_slots=32) for i in (1, 2, 3)]
    assert read(name, bare, modules) is None
    assert read(name, events, None) is None


def test_moe_expert_load_max_over_mean_is_the_median_over_steps_and_layers(
        read):
    name = "moe_expert_load_max_over_mean"
    events = [_line(i, **DECODE) for i in (1, 2, 3)]
    assert read(name, events, []) == pytest.approx(
        np.median([4, 3, 5, 4]) / 1.2)
    assert read(name, [], []) is None


def test_moe_prefill_mfu_counts_real_tokens_only(read):
    name = "moe_prefill_mfu"
    cfg = _file()
    pre = dict(moe_pairs=2048 * 24 + 768, moe_pairs_held=12_000 + 192,
               moe_decode_pairs=768, moe_decode_pairs_held=192,
               prefill_chunks=4, prefill_keys_needed=4 * 2048,
               decode_slots=32)
    events = [_line(1, **pre), _line(2, **DECODE)]
    modules = [("jit__prefill_chunk(2)", 0.0, 0.100),
               ("jit__decode_step(1)", 0.100, 0.120)]
    got = read(name, events, modules)
    flops = need.prefill_need_flops(cfg, tokens=2048, rows=4,
                                    keys_needed=4 * 2048, pairs_held=12_000,
                                    chunk=512)
    assert got == pytest.approx(100 * flops / 0.100 / 197e12)
    assert 0 < got < 100
    assert read(name, [_line(1, decode_slots=3)], modules) is None


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

    from huggingface_sagemaker_tensorflow_distributed_tpu.models.deepseek_v2 import (
        DeepseekV2ForCausalLM,
        deepseek_v2_config_from_hf,
    )
    from huggingface_sagemaker_tensorflow_distributed_tpu.serve import engine

    one_chip = SingleDeviceSharding(topo.devices[0])
    cfg = _file()
    dep = cfg["deployment"]
    model = DeepseekV2ForCausalLM(deepseek_v2_config_from_hf(
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
            pool_shapes)],
        param_bytes=sum(int(np.prod(l.shape)) * 2
                        for l in jax.tree_util.tree_leaves(pshape)))


def _total_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)


def test_sizes_are_the_published_ones(published):
    assert published["param_bytes"] == pytest.approx(10.33e9, rel=1e-3)
    assert published["token_bytes"] == 5 * 640 * 2     # 576 values in 640 lanes
    assert [p.shape[1:] for p in published["pools"]] == [(16, 640)] * 5


@pytest.mark.parametrize("bucket", [2048, 8192])
def test_decode_step_fits_at_both_buckets(published, bucket):
    q, n = published, published["dep"]["num_slots"]
    tables, ctx = q["rows"](n)
    step = jax.jit(
        lambda p, pools, *a: q["engine"]._decode_step(
            q["model"], p, pools, *a, q["plan"], bucket, False),
        donate_argnums=(1,))
    compiled = step.lower(q["params"], q["pools"], q["sds"]((n,), jnp.int32),
                          tables, ctx, q["sds"]((n,), jnp.bool_),
                          *q["sampling"](n)).compile()
    assert _total_bytes(compiled) <= HBM_BYTES
    text = compiled.as_text()
    # the pools are written where they lie: no re-layout of a whole pool
    assert "copy(%pools_" not in text
    assert "tpu_custom_call" in text              # the grouped matmuls


@pytest.mark.parametrize("bucket", [2048, 8192])
def test_four_row_prefill_fits_at_both_buckets(published, bucket):
    q, g, c = published, 4, published["dep"]["prefill_chunk"]
    tables, start = q["rows"](g)
    step = jax.jit(
        lambda p, pools, *a: q["engine"]._prefill_chunk(
            q["model"], p, pools, *a, q["plan"], False, bucket),
        donate_argnums=(1,))
    compiled = step.lower(q["params"], q["pools"], q["sds"]((g, c), jnp.int32),
                          tables, start, q["sds"]((g,), jnp.int32),
                          *q["sampling"](g)).compile()
    assert _total_bytes(compiled) <= HBM_BYTES
