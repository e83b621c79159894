"""The trace reduction: interval arithmetic on hand-made intervals, and
the whole reduction on a small trace recorded on one v5e chip by
``chipbench/tools/record_sample_trace.py`` (PR 22,
``data/v5e_1chip_sample.xplane.pb``): three steps of a matmul and a flash
forward + backward."""

import os

import pytest

from chipbench import reduce
from chipbench.reduce import Op, Trace

DATA = os.path.join(os.path.dirname(__file__), "data")


def test_merge_length_subtract_gaps():
    iv = [(0, 2), (1, 3), (5, 6), (6, 7), (9, 9)]
    assert reduce.merge(iv) == [(0, 3), (5, 7)]
    assert reduce.length(iv) == 5
    assert reduce.subtract([(0, 10)], [(2, 3), (5, 7)]) == [
        (0, 2), (3, 5), (7, 10)]
    assert reduce.subtract([(0, 4), (6, 8)], [(3, 7)]) == [(0, 3), (7, 8)]
    assert reduce.subtract([(1, 2)], [(0, 5)]) == []
    assert reduce.gaps(iv) == [(3, 5)]
    assert reduce.gaps(iv, window=(-1, 10)) == [(-1, 0), (3, 5), (7, 10)]


def _trace():
    ops = [
        # device 0: fusion 0-4, a nested op 1-2 (union must not add it),
        # all-reduce 4-6 alone (exposed 2), fusion 8-9
        Op(0, "fusion.1", 0.0, 4.0, "fusion f32[8]"),
        Op(0, "flash_fwd", 1.0, 2.0, "flash_fwd bf16[8]"),
        Op(0, "all-reduce.7", 4.0, 6.0, "all-reduce f32[8]"),
        Op(0, "fusion.2", 8.0, 9.0, "fusion f32[8]"),
        # device 1: all-reduce 0-3 overlapped by compute 0-2 (exposed 1)
        Op(1, "all-reduce.7", 0.0, 3.0), Op(1, "fusion.1", 0.0, 2.0),
    ]
    modules = [Op(0, "jit__decode_step(123)", 0.0, 6.0),
               Op(0, "jit__decode_step(123)", 8.0, 9.0),
               Op(0, "jit__prefill_chunk(9)", 6.0, 7.0)]
    notes = [Op(-1, "chipbench/engine.step", 5.5, 8.5),
             Op(-1, "chipbench/poll", 6.5, 7.9)]
    return Trace(ops, modules, notes)


def test_busy_is_the_union_averaged_over_devices():
    # device 0: [0,6] + [8,9] = 7; device 1: [0,3] = 3
    assert reduce.busy_seconds(_trace()) == pytest.approx(5.0)


def test_window_is_first_to_last_operation_averaged_over_devices():
    # device 0: 0..9; device 1: 0..3; busy (5) and exposed collective
    # (1.5) are parts of it, so no share of it passes 100%
    t = _trace()
    assert reduce.window_seconds(t) == pytest.approx(6.0)
    assert reduce.busy_seconds(t) <= reduce.window_seconds(t)
    assert reduce.window_seconds(Trace([], [], [])) == 0.0


@pytest.mark.parametrize("name,counters,want", [
    # 1000 B of weights + 5 blocks of 100 B over a limit of 4000 B
    ("hbm_live_share", {"param_bytes": 1000, "kv_held_blocks_peak": 5,
                        "block_bytes": 100}, 37.5),
    ("hbm_live_share", {"param_bytes": 1000, "kv_held_blocks_peak": None,
                        "block_bytes": 100}, None),
    # the longer of the trace's two prefill dispatches: 1 s
    ("prefill_dispatch_max_ms", {}, 1000.0),
    ("prefill_busy_share", {}, 100.0 * 1.0 / 5.0),
])
def test_serving_readers_on_the_hand_made_trace(name, counters, want):
    from chipbench import device, run

    o = device.Observed(
        cell=None, device_kind="TPU v5 lite", chips=1, window_s=10.0,
        values={}, counters=counters, events=[], trace=_trace(),
        trace_window_s=reduce.window_seconds(_trace()),
        memory_peak_bytes=3000, memory_limit_bytes=4000,
        compiles_in_window=0)
    got = run.read_layer(name, o)
    assert got == (pytest.approx(want) if want is not None else None)


def test_exposed_collective_is_collective_minus_compute():
    assert reduce.exposed_collective_seconds(_trace()) == pytest.approx(1.5)


def test_sums_counts_modules_and_tops():
    t = _trace()
    assert reduce.seconds_by_name(t, "flash_fwd") == pytest.approx(1.0)
    assert reduce.count_by_name(t, "fusion") == 2 and reduce.count_by_name(t, "fus") == 0
    assert reduce.module_seconds(t, "decode_step") == [6.0, 1.0]
    assert reduce.module_seconds(t, "prefill_chunk") == [1.0]
    top = reduce.top_ops(t, 2)
    assert top == [["decode_step: fusion f32[8]", 5.0],
                   ["decode_step: all-reduce f32[8]", 2.0]]


def test_parse_op_takes_the_operations_own_name_and_result_type():
    text = ("%fusion.1484 = (f32[16,512]{1,0:T(8,128)S(1)}, "
            "f32[16,512,8192]{2,1,0:T(8,128)}) fusion(bf16[16,512,8192]"
            "{2,1,0:T(8,128)(2,1)} %flash_fwd.34), kind=kOutput")
    assert reduce.parse_op(text) == (
        "fusion.1484", "fusion (f32[16,512], f32[16,512,8192])")
    # an operation that reads a kernel's result is not the kernel
    t = Trace([Op(0, *reduce.parse_op(text)[:1], 0.0, 1.0)], [], [])
    assert reduce.count_by_name(t, "flash_fwd") == 0
    assert reduce.parse_op("dot_general.1") == ("dot_general.1", "dot_general")


def test_idle_gaps_go_to_the_innermost_covering_host_span():
    # the one gap on device 0 is [6, 8], middle 7: poll (1.4 s long)
    # covers it and is shorter than engine.step (3 s)
    assert reduce.idle_gaps(_trace()) == [["chipbench/poll", 2.0]]
    bare = _trace()._replace(annotations=[])
    assert reduce.idle_gaps(bare) == [["unannotated", 2.0]]


@pytest.fixture(scope="module")
def one_chip():
    return reduce.load_trace(os.path.join(DATA, "v5e_1chip_sample.xplane.pb"))


def test_one_chip_trace_modules_kernels_and_busy(one_chip):
    t = one_chip
    assert {o.device for o in t.ops} == {0}
    steps = reduce.module_seconds(t, "sample_step")
    assert len(steps) == 3 and all(40e-6 < s < 70e-6 for s in steps)
    for kernel in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        assert reduce.count_by_name(t, kernel) == 3, kernel
    # the kernels are most of a step; nothing runs outside the modules
    kernels = sum(reduce.seconds_by_name(t, k) for k in
                  ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"))
    busy = reduce.busy_seconds(t)
    assert 0.5 * busy < kernels < busy <= sum(steps) + 1e-9
    top = reduce.top_ops(t, 3)
    assert top[0][0].startswith("sample_step: flash_fwd ")
    # idle between the steps: everything from the first op to the last
    # that is not busy, attributed to the benchmark's own spans
    lo = min(o.start_s for o in t.ops)
    hi = max(o.end_s for o in t.ops)
    gaps = reduce.idle_gaps(t)
    assert sum(s for _, s in gaps) == pytest.approx(hi - lo - busy)
    assert all(name.startswith("chipbench/") or name == "unannotated"
               for name, _ in gaps)
