"""The program map (ISSUE 37, ``obs/programs.py``): a compiled program's
text taken apart and classified by the program's own modules, the
registry that waits for a sink, and the ``program_map`` event's schema."""

import json
import subprocess
import sys

import numpy as np
import pytest

from huggingface_sagemaker_tensorflow_distributed_tpu import obs
from huggingface_sagemaker_tensorflow_distributed_tpu.obs import programs
from huggingface_sagemaker_tensorflow_distributed_tpu.obs.schema import (
    PROGRAM_COMPONENTS,
    validate_event,
)

GEOMETRY = dict(num_slots=2, block_size=4, num_blocks=40, prefill_chunk=8,
                max_model_len=64)

# what a TPU's compiler prints, cut to what the parser reads: an entry
# with a prefetched weight, a fused matmul that carries a norm's
# multiply, a loop with a body of its own, a reducer, a nameless copy of
# a pool, the head and the token pick, tuples and parameters between
TEXT = '''HloModule jit__prefill_chunk, is_scheduled=true, entry_computation_layout={()->()}

%region_0.1 (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %add.9 = f32[] add(%a, %b), metadata={op_name="jit(_prefill_chunk)/M/backbone/layers_0/input_ln/reduce_sum"}
}

%fused_computation.1 (p0: bf16[8,64], p1: bf16[64,32], p2: f32[8]) -> bf16[8,32] {
  %p0 = bf16[8,64]{1,0} parameter(0)
  %p1 = bf16[64,32]{1,0} parameter(1)
  %p2 = f32[8]{0} parameter(2)
  %mul.3 = bf16[8,64]{1,0} multiply(%p0, %p0), metadata={op_name="jit(_prefill_chunk)/M/backbone/layers_0/input_ln/mul"}
  ROOT %dot.4 = bf16[8,32]{1,0:T(8,128)(2,1)} dot(%mul.3, %p1), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name="jit(_prefill_chunk)/M/backbone/layers_0/self_attn/q_proj/dot_general"}
}

%body.2 (t: (s32[], f32[8,32])) -> (s32[], f32[8,32]) {
  %t = (s32[], f32[8,32]{1,0}) parameter(0)
  %gte.1 = f32[8,32]{1,0} get-tuple-element(%t), index=1
  %fusion.7 = f32[8,32]{1,0} fusion(%gte.1), kind=kLoop, calls=%fused_computation.9, metadata={op_name="jit(_prefill_chunk)/M/backbone/layers_1/linear_attn/while/body/mul"}
  ROOT %tuple.5 = (s32[], f32[8,32]{1,0}) tuple(%gte.1, %fusion.7)
}

%fused_computation.9 (q0: f32[8,32]) -> f32[8,32] {
  %q0 = f32[8,32]{1,0} parameter(0)
  ROOT %mul.8 = f32[8,32]{1,0} multiply(%q0, %q0), metadata={op_name="jit(_prefill_chunk)/M/backbone/layers_1/linear_attn/while/body/mul"}
}

ENTRY %main.1 (params: bf16[64,32], pool: bf16[40,4,2,16], x: bf16[8,64]) -> (s32[8], bf16[40,4,2,16]) {
  %params = bf16[64,32]{1,0} parameter(0)
  %pool = bf16[40,4,2,16]{3,2,1,0} parameter(1), metadata={op_name="pools[0]"}
  %x = bf16[8,64]{1,0} parameter(2)
  %copy-start.1 = (bf16[64,32]{1,0:S(1)}, bf16[64,32]{1,0}, u32[]) copy-start(%params)
  %copy-done.1 = bf16[64,32]{1,0:S(1)} copy-done(%copy-start.1)
  %copy.3 = bf16[40,4,2,16]{3,2,1,0} copy(%pool)
  %reduce.2 = f32[8]{0} reduce(%x, %x), dimensions={1}, to_apply=%region_0.1, metadata={op_name="jit(_prefill_chunk)/M/backbone/layers_0/input_ln/reduce_sum"}
  %fusion.1 = bf16[8,32]{1,0:T(8,128)(2,1)} fusion(%x, %copy-done.1, %reduce.2), kind=kOutput, calls=%fused_computation.1, metadata={op_name="jit(_prefill_chunk)/M/backbone/layers_0/self_attn/q_proj/dot_general"}
  %copy.4 = bf16[8,32]{0,1} copy(%fusion.1)
  %tuple.1 = (s32[], bf16[8,32]{1,0}) tuple(%fusion.1, %fusion.1)
  %while.1 = (s32[], f32[8,32]{1,0}) while(%tuple.1), condition=%body.2, body=%body.2, metadata={op_name="jit(_prefill_chunk)/M/backbone/layers_1/linear_attn/while"}
  %gte.2 = f32[8,32]{1,0} get-tuple-element(%while.1), index=1
  %add.5 = f32[8,32]{1,0} add(%gte.2, %gte.2), metadata={op_name="jit(_prefill_chunk)/M/backbone/layers_1/add"}
  %dot.6 = f32[8,128]{1,0} dot(%add.5, %add.5), metadata={op_name="jit(_prefill_chunk)/M/bsh,vh->bsv/dot_general"}
  %gather.1 = f32[8,128]{1,0} gather(%dot.6, %x), metadata={op_name="jit(_prefill_chunk)/jit(take_along_axis)/gather"}
  %reduce.3 = s32[8]{0} reduce(%gather.1, %gather.1), dimensions={1}, to_apply=%region_0.1, metadata={op_name="jit(_prefill_chunk)/argmax"}
  %scatter.1 = bf16[40,4,2,16]{3,2,1,0} scatter(%copy.3, %x, %fusion.1), to_apply=%region_0.1, metadata={op_name="jit(_prefill_chunk)/scatter"}
  %all-reduce.1 = bf16[8,32]{1,0} all-reduce(%fusion.1), to_apply=%region_0.1
  ROOT %tuple.2 = (s32[8]{0}, bf16[40,4,2,16]{3,2,1,0}) tuple(%reduce.3, %scatter.1)
}
'''


def test_a_compiled_text_is_taken_apart_and_classified():
    m = programs.build_map(TEXT, root="M")
    assert m["program"] == "prefill_chunk" and not m["training"]
    ops = m["ops"]
    # what never runs as an operation of its own has no row; nor has
    # what is inside a fusion or a reducer
    for silent in ("params", "tuple.1", "gte.2", "mul.3", "dot.4", "add.9",
                   "mul.8", "t"):
        assert silent not in ops
    component = {name: row[1] for name, row in ops.items()}
    assert component == {
        "copy-start.1": "mixer",    # a prefetch: what reads it decides
        "copy-done.1": "mixer",
        "copy.3": "cache",          # a nameless copy of a pool's shape
        "copy.4": "mixer",          # any other takes after what it copies
        "reduce.2": "residual",
        "fusion.1": "mixer",        # the root decides ...
        "while.1": "mixer",         # the recurrence, and inside its body
        "fusion.7": "mixer",
        "add.5": "residual",        # an add directly under the layer
        "dot.6": "head",            # a tied head, under the model itself
        "gather.1": "head",         # reads the logits: the token pick
        "reduce.3": "head",
        "scatter.1": "cache",       # rows written back
        "all-reduce.1": "collective",
    }
    # ... and the fusion says what else it holds
    assert ops["fusion.1"][3] is True and ops["fusion.1"][4] == ["residual"]
    assert ops["fusion.7"][3] is False and len(ops["fusion.7"]) == 4
    # result types as a trace's event line gives them: no layouts
    assert ops["fusion.1"][2] == "bf16[8,32]"
    assert ops["copy-start.1"][2] == "(bf16[64,32], bf16[64,32], u32[])"
    # numbered layers collapse; the scope is the path below the primitive
    scopes = m["scopes"]
    assert scopes[ops["fusion.1"][0]] == [
        "M/backbone/layers_*/self_attn/q_proj", ""]
    assert scopes[ops["fusion.7"][0]] == [
        "M/backbone/layers_*/linear_attn/while/body", ""]
    assert set(component.values()) <= set(PROGRAM_COMPONENTS)


@pytest.mark.parametrize("op_name, root, training, want, which", [
    ("jit(f)/jit(main)/Model/backbone/layers_12/attn_hc/self_attn/o_proj/"
     "dot_general", "Model", False, "mixer", ""),
    ("jit(f)/Model/backbone/layers_12/attn_hc/mul", "Model", False,
     "residual", ""),
    ("jit(f)/Model/backbone/layers_3/self_attn/kv_a_ln/mul", "Model", False,
     "mixer", ""),
    ("jit(f)/Model/backbone/layers_3/moe/shared_experts/up_proj/dot_general",
     "Model", False, "ffn", ""),
    ("jit(f)/Model/lm_head/dot_general", "Model", False, "head", ""),
    ("jit(f)/Model/backbone/embed_tokens/jit(_take)/gather", "Model", False,
     "embed", ""),
    # the engine's own scopes refine; without them the primitive decides
    ("jit(f)/serve/cache_read/transpose", "Model", False, "cache", ""),
    ("jit(f)/transpose", "Model", False, None, ""),
    ("jit(f)/vmap()/dynamic_update_slice", "Model", False, "cache", ""),
    ("jit(f)/serve/sample/argmax", "Model", False, "head", ""),
    ("jit(f)/argmax", "Model", False, "head", ""),
    # a scope around the model's call decides nothing inside the model
    ("jit(step)/jvp(train/loss)/Model/backbone/sub", "Model", True, None,
     "fwd"),
    ("jit(step)/jvp(train/loss)/Model/encoder/layer_3/ffn/Dropout_0/mul",
     "Model", True, "residual", "fwd"),
    ("jit(step)/transpose(jvp(Model))/encoder/layer_3/attention/query/"
     "dot_general", "Model", True, "mixer", "bwd"),
    # a train step outside the model: differentiated is the loss
    ("jit(step)/jvp(jit(softmax_ce))/exp", "Model", True, "head", "fwd"),
    ("jit(step)/transpose(jvp(jit(softmax_ce)))/mul", "Model", True, "head",
     "bwd"),
    ("jit(step)/mul", "Model", True, "optimizer", ""),
    ("jit(step)/train/optimizer/sqrt", "Model", True, "optimizer", ""),
])
def test_a_path_gives_its_component_and_its_pass(op_name, root, training,
                                                 want, which):
    path = programs.split_path(op_name, root)
    assert path.which == which
    assert programs.classify(path, "fusion", training, False) == want


def test_the_short_name_is_the_trace_reductions():
    from chipbench import reduce

    for module in ("jit__prefill_chunk", "jit__train_step_impl", "jit_f",
                   "main"):
        find = reduce._module_of(reduce.Trace(
            [], [reduce.Op(0, module + "(123)", 0.0, 1.0)], []), 0)
        assert programs.short_name(module) == find(0.5)


# -- the registry ---------------------------------------------------------------

def _events(out):
    path = out / "events.jsonl"
    if not path.exists():
        return []
    return [e for _, e, err in obs.iter_events(str(path)) if err is None]


def _engine(gpt2_setup, **kw):
    from huggingface_sagemaker_tensorflow_distributed_tpu.serve.engine import (
        ServeEngine,
    )

    _cfg, model, params = gpt2_setup
    return ServeEngine(model, params, **GEOMETRY, **kw)


def test_without_a_sink_nothing_resolves_and_nothing_is_written(
        gpt2_setup, tmp_path, monkeypatch):
    obs.reset(enabled=True)                 # no directory
    built = []
    monkeypatch.setattr(programs, "build_map", lambda *a, **k: built.append(a))
    try:
        eng = _engine(gpt2_setup)
        eng.warmup()
        # prefill [1, C] and [prefill_batch, C] at the buckets a chunk
        # fits, a decode step a bucket: each once, though warm-up runs
        # the decode step twice
        n = len(programs.registry())
        assert n == len({(1, eng.prefill_buckets[0])}
                        | {(eng.prefill_batch, w)
                           for w in eng.prefill_buckets}) \
            + len(eng.gather_buckets)
        eng.warmup()                        # idempotent: nothing new
        _engine(gpt2_setup).warmup()        # the same programs again
        assert len(programs.registry()) == n
        obs.flush()
        obs.shutdown()
        assert built == []
        assert not list(tmp_path.iterdir())
    finally:
        obs.reset()


def test_a_sink_gets_one_map_a_program_once(gpt2_setup, tmp_path):
    out = tmp_path / "telemetry"
    obs.reset(enabled=True)
    try:
        eng = _engine(gpt2_setup)
        eng.warmup()            # before the directory, as the benchmark does
        obs.configure(out_dir=str(out), enabled=True)
        obs.flush()
        obs.flush()             # resolved once, written once
        maps = [e for e in _events(out) if e["type"] == "program_map"]
        assert len(maps) == len(programs.registry())
        assert {m["program"] for m in maps} == {"prefill_chunk",
                                                "decode_step"}
        assert sorted(m["key"]["rows"] for m in maps
                      if m["program"] == "prefill_chunk") \
            == sorted([1] + [eng.prefill_batch] * len(eng.prefill_buckets))
        for m in maps:
            assert validate_event(m) == [], m["key"]
            assert m["resolve_s"] > 0 and m["from_cache"] is False
            assert m["refined_by"] == ["serve/cache_read",
                                       "serve/cache_write", "serve/sample"]
            got = {row[1] for row in m["ops"].values()}
            assert {"mixer", "ffn", "head", "cache"} <= got
        # no live array is kept: shapes only
        import jax

        for entry in programs.registry()._entries.values():
            leaves = jax.tree_util.tree_leaves(
                [a for i, a in enumerate(entry.args) if i not in (0, 12)])
            assert all(isinstance(x, (jax.ShapeDtypeStruct, bool, int))
                       for x in leaves)
    finally:
        obs.reset()


def test_the_registry_keeps_the_last_programs_registered():
    reg = programs.ProgramRegistry()

    def f(x):
        return x

    for i in range(programs.MAX_PROGRAMS + 6):
        assert reg.register("step", f, (np.zeros((2,), np.float32),),
                            key={"bucket": i})
    assert len(reg) == programs.MAX_PROGRAMS and reg.dropped == 6
    kept = sorted(e.key["bucket"] for e in reg._entries.values())
    assert kept[0] == 6 and kept[-1] == programs.MAX_PROGRAMS + 5
    # the same callable under the same key is there already
    assert not reg.register("step", f, (np.zeros((2,), np.float32),),
                            key={"bucket": 10})


def test_a_program_that_cannot_be_resolved_is_an_alert_not_a_crash(tmp_path):
    out = tmp_path / "telemetry"
    obs.reset(out_dir=str(out), enabled=True)
    try:
        programs.register("step", lambda x: x, (np.zeros((2,), np.float32),))
        obs.flush()             # a plain function has no .lower
        alerts = [e for e in _events(out) if e["type"] == "alert"]
        assert len(alerts) == 1 and alerts[0]["name"] == "program_map"
        assert "no map" in alerts[0]["message"]
    finally:
        obs.reset()


def _count_calls(monkeypatch):
    calls = []
    for name in ("register", "flush"):
        real = getattr(programs, name)
        monkeypatch.setattr(
            programs, name,
            lambda *a, _real=real, _name=name, **k: (
                calls.append(_name), _real(*a, **k))[1])
    return calls


def test_the_engines_step_makes_no_call_into_the_program_map(
        gpt2_setup, tmp_path, monkeypatch):
    obs.reset(out_dir=str(tmp_path / "telemetry"), enabled=True)
    try:
        eng = _engine(gpt2_setup)
        eng.warmup()
        calls = _count_calls(monkeypatch)
        rng = np.random.RandomState(4)
        for n in (5, 11, 7, 9, 6, 10):
            eng.submit(rng.randint(1, 120, (n,)).astype(np.int32), 8)
        steps = 0
        while eng.has_work() and steps < 40:
            eng.step()
            steps += 1
        assert steps >= 20 and calls == []
    finally:
        obs.reset()


def test_the_trainers_loop_makes_no_call_into_the_program_map(
        tmp_path, monkeypatch, devices8):
    import jax

    from huggingface_sagemaker_tensorflow_distributed_tpu.config import (
        TrainConfig,
    )
    from huggingface_sagemaker_tensorflow_distributed_tpu.data import (
        ArrayDataset,
        ShardedBatcher,
        WordHashTokenizer,
    )
    from huggingface_sagemaker_tensorflow_distributed_tpu.models.auto import (
        init_params,
    )
    from huggingface_sagemaker_tensorflow_distributed_tpu.models.bert import (
        BertForSequenceClassification,
    )
    from huggingface_sagemaker_tensorflow_distributed_tpu.models.layers import (
        EncoderConfig,
    )
    from huggingface_sagemaker_tensorflow_distributed_tpu.parallel import (
        MeshConfig,
        build_mesh,
    )
    from huggingface_sagemaker_tensorflow_distributed_tpu.train import Trainer

    out = tmp_path / "telemetry"
    obs.reset(out_dir=str(out), enabled=True)
    try:
        cfg = EncoderConfig(vocab_size=64, hidden_size=16, num_layers=1,
                            num_heads=2, intermediate_size=32,
                            max_position_embeddings=32)
        model = BertForSequenceClassification(cfg, num_labels=2)
        params = init_params(model, cfg, seed=0)
        tcfg = TrainConfig(dtype="float32", train_batch_size=2,
                           max_seq_length=16, log_every_steps=0)
        mesh = build_mesh(MeshConfig(dp=-1), devices=devices8[:1])
        trainer = Trainer(tcfg, model, params, mesh)
        texts = [f"w{i} w{i + 1} w{i + 2}" for i in range(48)]
        ds = ArrayDataset.from_texts(WordHashTokenizer(), texts,
                                     [i % 2 for i in range(48)],
                                     max_length=16)
        batcher = ShardedBatcher(ds, 2, mesh, shuffle=False, seed=0)
        first = trainer._train_step
        trainer.fit(batcher, epochs=1)      # 24 steps; the first registers
        assert trainer._train_step is not first
        assert len(programs.registry()) == 1
        calls = _count_calls(monkeypatch)
        trainer.fit(batcher, epochs=1)      # 24 more
        assert calls == []
        # the fit's own flush wrote no map: a fit may lie inside a window
        # somebody times; the caller's flush does
        assert not [e for e in _events(out) if e["type"] == "program_map"]
        monkeypatch.undo()
        obs.flush()
        maps = [e for e in _events(out) if e["type"] == "program_map"]
        assert len(maps) == 1 and maps[0]["program"] == "train_step_impl"
        assert maps[0]["training"] and maps[0]["key"] == {"batch": "2x16"}
        assert maps[0]["refined_by"] == ["train/loss", "train/optimizer"]
        passes = {which for _path, which in maps[0]["scopes"]}
        assert passes == {"", "fwd", "bwd"}
        # the registry does not keep a trainer alive
        del trainer, first
        import gc

        gc.collect()
        jax.clear_caches()
        gc.collect()
        assert all(e.jitted() is None
                   for e in programs.registry()._entries.values())
    finally:
        obs.reset()


# -- the schema -------------------------------------------------------------------

def _stamped(fields: dict) -> dict:
    return {"v": 1, "t": 1.0, "host": 0, "pid": 1, "type": "program_map",
            **fields}


GOOD = {"program": "prefill_chunk", "key": {"rows": 4, "width": 2048},
        "scopes": [["M/backbone/layers_*/mlp", ""]],
        "ops": {"fusion.1": [0, "ffn", "bf16[4,512]", False],
                "copy.2": [-1, "cache", "bf16[8]", True, ["mixer"]]},
        "resolve_s": 0.5, "from_cache": True}


@pytest.mark.parametrize("change, fault", [
    ({}, None),
    ({"ops": {"fusion.1": [0, "matmuls", "bf16[4,512]", False]}},
     "unknown component 'matmuls'"),
    ({"ops": {"fusion.1": [3, "ffn", "bf16[4,512]", False]}},
     "names scope 3 of 1"),
    ({"ops": {"fusion.1": ["ffn", 0]}},
     "is not [scope index, component, result type, mixed]"),
    ({"scopes": [["M/mlp", "sideways"]]}, "scopes[0] is not"),
    ({"resolve_s": "soon"}, "field 'resolve_s' has type str"),
    ({"from_cache": 1}, "field 'from_cache' has type int"),
])
def test_a_malformed_program_map_is_named(change, fault, tmp_path):
    event = _stamped({**GOOD, **change})
    errors = validate_event(event)
    if fault is None:
        assert errors == []
    else:
        assert any(fault in e for e in errors), errors
    # and by the lint's command line, on a file
    path = tmp_path / "events.jsonl"
    path.write_text(json.dumps(_stamped(GOOD)) + "\n" + json.dumps(event)
                    + "\n")
    proc = subprocess.run(
        [sys.executable, "scripts/check_telemetry_schema.py", str(path)],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
    assert proc.returncode == (0 if fault is None else 1), proc.stdout
    if fault is not None:
        assert f"{path}:2" in proc.stdout and fault in proc.stdout
