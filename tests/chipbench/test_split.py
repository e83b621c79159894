"""``chipbench/split.py`` on hand-made traces: an operation's self time,
the map that covers a module, what no table holds, and the eleven
readers on top (ISSUE 37)."""

import importlib

import pytest

from chipbench import device, reduce, spec, split
from chipbench.reduce import Op, Trace
from chipbench.tools import program_split

NEW = ("prefill_mixer_ms", "prefill_ffn_ms", "prefill_residual_ms",
       "prefill_cache_ms", "prefill_head_ms", "scope_coverage_share",
       "train_mixer_ms", "train_ffn_ms", "train_residual_ms",
       "train_optimizer_ms", "train_scope_coverage_share")


def _map(program, key, ops, scopes=(("M/layers_*/x", ""),)):
    return {"type": "program_map", "program": program, "key": key,
            "scopes": [list(s) for s in scopes], "ops": ops,
            "resolve_s": 0.1, "from_cache": False}


def _op(name, start, end, result):
    return Op(0, name, start, end, (reduce._base(name) + " " + result).strip())


# two prefill programs of one name: one row and four. `fusion.1` is in
# both tables with another result type; `fusion.9` only in the second
ONE_ROW = _map("prefill_chunk", {"rows": 1, "width": 64}, {
    "fusion.1": [0, "mixer", "bf16[1,16]", False],
    "fusion.2": [0, "ffn", "bf16[1,64]", True, ["residual"]],
    "copy.3": [-1, "cache", "bf16[40,4]", False]})
FOUR_ROWS = _map("prefill_chunk", {"rows": 4, "width": 64}, {
    "fusion.1": [0, "mixer", "bf16[4,16]", False],
    "fusion.9": [0, "head", "f32[4,128]", False],
    "while.4": [0, "mixer", "(s32[], f32[4,8])", False],
    "fusion.5": [0, "residual", "f32[4,8]", False]})
DECODE = _map("decode_step", {"bucket": 64, "slots": 2}, {
    "fusion.1": [0, "ffn", "bf16[2,64]", False],
    "fusion.6": [0, "other", "s32[2]", False]})

TRACE = Trace(
    ops=[
        # module A, the one-row program: 1.0 .. 2.0, busy 0.9
        _op("fusion.1", 1.0, 1.4, "bf16[1,16]"),
        _op("fusion.2", 1.4, 1.7, "bf16[1,64]"),
        _op("copy.3", 1.8, 2.0, "bf16[40,4]"),
        # module B, the four-row program: 3.0 .. 4.0; a while of 0.6 s
        # whose body runs twice inside it, and an operation in no table
        _op("fusion.1", 3.0, 3.1, "bf16[4,16]"),
        _op("while.4", 3.1, 3.7, "(s32[], f32[4,8])"),
        _op("fusion.5", 3.2, 3.4, "f32[4,8]"),
        _op("fusion.5", 3.45, 3.65, "f32[4,8]"),
        _op("fusion.77", 3.7, 3.8, "f32[3]"),
        _op("fusion.9", 3.8, 4.0, "f32[4,128]"),
        # module C, a decode step
        _op("fusion.1", 5.0, 5.5, "bf16[2,64]"),
        _op("fusion.6", 5.5, 5.6, "s32[2]"),
        # a program nobody registered
        _op("select.1", 6.0, 6.1, "s32[2]"),
        # another device: not read
        Op(1, "fusion.1", 1.0, 9.0, "fusion bf16[1,16]"),
    ],
    modules=[Op(0, "jit__prefill_chunk(11)", 1.0, 2.0),
             Op(0, "jit__prefill_chunk(22)", 3.0, 4.0),
             Op(0, "jit__decode_step(33)", 5.0, 5.6),
             Op(0, "jit__where(44)", 6.0, 6.1),
             Op(1, "jit__prefill_chunk(11)", 1.0, 9.0)],
    annotations=[])
MAPS = [ONE_ROW, FOUR_ROWS, DECODE]


def _observed(trace, events, cell=None):
    return device.Observed(
        cell=cell, device_kind="cpu", chips=1, window_s=2.0, values={},
        counters={}, events=events, trace=trace, trace_window_s=0.0,
        memory_peak_bytes=0, memory_limit_bytes=0, compiles_in_window=0)


def test_two_programs_of_one_name_are_told_apart_by_their_tables():
    a, b = split.split_modules(TRACE, MAPS, "prefill_chunk")
    assert a.map is ONE_ROW and b.map is FOUR_ROWS
    assert a.seconds() == pytest.approx(
        {"mixer": 0.4, "ffn": 0.3, "cache": 0.2})
    assert [r.mixed for r in a.rows] == [False, True, False]
    # `fusion.1` of the other program's table is not this module's
    wrong = split.split_modules(TRACE, [FOUR_ROWS], "prefill_chunk")[0]
    assert wrong.seconds() == pytest.approx({split.UNMAPPED: 0.9})


def test_a_while_and_its_body_are_not_counted_twice():
    b = split.split_modules(TRACE, MAPS, "prefill_chunk")[1]
    got = b.seconds()
    # the while's 0.6 s hold 0.4 s of its body's operations
    assert got == pytest.approx({"mixer": 0.1 + 0.2, "residual": 0.4,
                                 split.UNMAPPED: 0.1, "head": 0.2})
    assert sum(got.values()) == pytest.approx(b.busy_s)
    assert b.busy_s == pytest.approx(1.0)


def test_components_with_unmapped_sum_to_each_modules_busy_seconds():
    for s in split.split_modules(TRACE, MAPS, ""):
        assert sum(s.seconds().values()) == pytest.approx(s.busy_s)
        assert s.busy_s == pytest.approx(reduce.length(
            (r.op.start_s, r.op.end_s) for r in s.rows))
    where = split.split_modules(TRACE, MAPS, "where")[0]
    assert where.map is None
    assert where.seconds() == pytest.approx({split.UNMAPPED: 0.1})


def test_self_seconds_partition_the_union_whatever_the_nesting():
    ops = [_op("a", 0.0, 1.0, ""), _op("b", 0.2, 0.5, ""),
           _op("c", 0.3, 0.4, ""), _op("d", 0.9, 1.2, ""),   # past a's end
           _op("e", 2.0, 2.5, "")]
    own = split.self_seconds(ops)
    assert own == pytest.approx([0.6, 0.2, 0.1, 0.3, 0.5])
    assert sum(own) == pytest.approx(reduce.length(
        (o.start_s, o.end_s) for o in ops))


def test_component_seconds_and_what_the_readers_read():
    o = _observed(TRACE, MAPS)
    assert split.component_seconds(o, "prefill_chunk") == pytest.approx(
        {"mixer": 0.7, "ffn": 0.3, "cache": 0.2, "residual": 0.4,
         "head": 0.2, split.UNMAPPED: 0.1})
    assert split.component_seconds(o, "decode_step") == pytest.approx(
        {"ffn": 0.5, "other": 0.1})
    # a mean over the two dispatches, in milliseconds
    assert split.prefill_ms(o, "mixer") == pytest.approx(350.0)
    assert split.prefill_ms(o, "optimizer") == 0.0
    # everything named, less `unmapped` (0.1 + 0.1) and `other` (0.1),
    # over device 0's busy seconds
    assert split.coverage_share(o) == pytest.approx(
        100.0 * (2.6 - 0.3) / 2.6)
    assert split.train_ms(o, "mixer") is None      # no train step ran


def test_a_train_step_is_read_at_the_median_module():
    step = _map("train_step_impl", {"batch": "16x512"}, {
        "fusion.1": [0, "mixer", "bf16[16,512]", False],
        "fusion.2": [1, "optimizer", "f32[1024]", False]},
        scopes=(("M/layer_*/attention", "bwd"), ("train/optimizer", "")))
    trace = Trace(
        ops=[_op("fusion.1", t, t + d, "bf16[16,512]")
             for t, d in ((0.0, 0.1), (1.0, 0.2), (2.0, 0.9))]
        + [_op("fusion.2", t + 0.9, t + 1.0, "f32[1024]")
           for t in (0.0, 1.0, 2.0)],
        modules=[Op(0, "jit__train_step_impl(1)", t, t + 1.0)
                 for t in (0.0, 1.0, 2.0)],
        annotations=[])
    o = _observed(trace, [step])
    assert split.train_ms(o, "mixer") == pytest.approx(200.0)
    assert split.train_ms(o, "optimizer") == pytest.approx(100.0)


@pytest.mark.parametrize("name", NEW)
def test_a_reader_finds_nothing_without_a_trace_or_a_map(name):
    read = importlib.import_module("chipbench.layers." + name).read
    assert read(_observed(None, MAPS)) is None
    # the parent's program writes no program_map: nothing, and no error
    assert read(_observed(TRACE, [{"type": "span", "name": "x"}])) is None
    got = read(_observed(TRACE, MAPS))
    assert got is None if name.startswith("train_") and "coverage" not in name \
        else got >= 0.0


def test_a_cpu_traces_events_match_by_name_alone():
    # a CPU run's events carry no result type (reduce.load_trace)
    trace = Trace(ops=[Op(0, "fusion.1", 0.0, 1.0, "fusion"),
                       Op(0, "dot.7", 1.0, 2.0, "dot")],
                  modules=[Op(0, "jit__decode_step", 0.0, 2.0)],
                  annotations=[])
    s, = split.split_modules(trace, [DECODE], "decode_step")
    assert s.seconds() == pytest.approx({"ffn": 1.0, split.UNMAPPED: 1.0})


def test_the_tool_prints_every_program_by_key_and_component():
    lines = program_split.tables(split.split_modules(TRACE, MAPS, ""), top=2)
    text = "\n".join(lines)
    assert "== prefill_chunk {'rows': 1, 'width': 64}: 1 dispatch(es), " \
           "1000.000 ms a dispatch (busy 900.000)" in text
    assert "== prefill_chunk {'rows': 4, 'width': 64}: 1 dispatch(es)" in text
    assert "== decode_step {'bucket': 64, 'slots': 2}" in text
    assert "== where : 1 dispatch(es)" in text
    four = text.split("'rows': 4")[1].split("==")[0]
    assert "residual" in four and "400.000 ms" in four
    assert "fusion bf16[1,64]  [mixed]" in text
    assert "unmapped" in four and "fusion f32[3]" in four


def test_the_committed_benchmark_lists_the_eleven_and_has_no_fault():
    bench = spec.load_benchmark()
    assert spec.check(bench) == []
    names = [m["name"] for m in bench["per_layer"]]
    assert names[-len(NEW):] == list(NEW)       # appended, in this order
    serving = [w["name"] for w in bench["workloads"]
               if w["traffic"].endswith("-sat")]
    training = [w["name"] for w in bench["workloads"]
                if w["name"] not in serving]
    for m in bench["per_layer"][-len(NEW):]:
        assert m["source"] == "device_trace"
        assert m["workloads"] == (training if m["name"].startswith("train_")
                                  else serving)
        assert m["better"] == ("higher" if "coverage" in m["name"]
                               else "lower")
