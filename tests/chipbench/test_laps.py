"""A closed loop run as laps: every whole lap is the same work, the rate
is the median lap's, the tail is over the whole laps' gaps, and a lap's
engine is gone before the next one."""

import gc
import json
import math
import time

import numpy as np
import pytest

from chipbench import loadgen, run, spec, stats
from chipbench.kinds import serve
from chipbench.kinds.serve import Lap, itl_gaps, lap_rate

CELL = "qwen2.5-3b-chat-sat"
OLMO = "olmo-hybrid-7b-pp2-gen-sat"

# one lap's gaps as Olmo's cell makes them, in time order: 47 first-step
# gaps behind the opening's last dispatches, decode steps, eleven
# iterations of successors' prefill beside 64 decoding slots, the 64 gaps
# of iteration 139 (a four-row dispatch, a one-row one and a step), and
# decode steps to the lap's end; the 99th percentile of a lap is the
# fourth of those 64 from below
LAP_GAPS = ([0.70] * 47 + [0.0203] * 8_000
            + [0.055 + 0.012 * i for i in range(11) for _ in range(64)]
            + [0.219] * 64 + [0.0203] * 1_885)


@pytest.mark.parametrize("stalled", [None, 0, 1, 2])
def test_one_stalled_lap_of_three_does_not_move_the_rate(stalled):
    laps = [Lap(140, 2231, 15.31 + (1.4 if i == stalled else 0.0), True)
            for i in range(3)]
    laps.append(Lap(37, 400, 4.2, False))       # the cut lap never counts
    assert lap_rate(laps) == pytest.approx(2231 / 15.31)


def test_rate_of_an_even_number_of_laps_and_of_none():
    laps = [Lap(10, 100, s, True) for s in (1.0, 1.0, 1.0, 2.0)]
    assert lap_rate(laps) == pytest.approx(100.0)
    assert lap_rate([Lap(3, 30, 0.5, False)]) is None
    assert lap_rate([]) is None


def _laps(k: int, cut: list):
    """``Driver.gaps`` and ``Driver.laps`` of a window that held ``k``
    whole laps of ``LAP_GAPS`` (in time order, as a lap makes them) and
    then a lap cut after the gaps ``cut``."""
    gaps, laps, t = [], [], 0.0
    for i, lap in enumerate([LAP_GAPS] * k + [cut]):
        first = len(gaps)
        for g in lap:
            t += g
            gaps.append((t, g))
        laps.append(Lap(170, 10_860, 7.44, i < k, slice(first, len(gaps))))
    return gaps, laps, t


@pytest.mark.parametrize("k, cut, moves_every_gap", [
    (5, [], False), (6, [], False), (7, [], False),
    (6, [2.5] * 4_000, True),           # a cut lap of huge gaps
    (6, LAP_GAPS[:8_500], True),        # cut inside the successors' prefill
    (5, LAP_GAPS[:3_000], False),       # cut early in the decode steps
])
def test_the_tail_of_whole_laps_does_not_depend_on_the_cut(k, cut,
                                                           moves_every_gap):
    gaps, laps, window_s = _laps(k, cut)
    got = itl_gaps(gaps, laps, window_s)
    assert len(got) == k * len(LAP_GAPS)
    # every whole lap is the same work, so k of them read the rank of
    # one, on iteration 139's plateau, whatever k is and wherever the
    # window cut the last lap
    assert stats.percentile(got, 99.0) == 0.219
    assert stats.percentile(got, 99.0) == stats.percentile(
        [g for lap in laps[:k] for _, g in gaps[lap.gaps]], 99.0)
    # the window's every gap reads the cut lap too, and a cut inside the
    # successors' prefill moves the rank off the plateau
    stamped = itl_gaps(gaps, None, window_s)
    assert len(stamped) == len(gaps) == len(got) + len(cut)
    assert (stats.percentile(stamped, 99.0) != 0.219) == moves_every_gap


def test_without_laps_every_gap_stamped_inside_the_window_counts():
    gaps = [(0.5, 0.1), (1.0, 0.5), (2.0, 1.0), (3.5, 1.5)]
    # an open loop's one serve() is a lap that is never whole
    laps = [Lap(40, 4, 3.5, False, slice(0, 4))]
    assert itl_gaps(gaps, None, 2.0) == [0.1, 0.5, 1.0]
    assert itl_gaps(gaps, laps, 2.0) == []


def _keep_drivers(monkeypatch) -> list:
    """The window's driver, kept past the run; the dry lap's and the
    traced lap's (no window: infinite seconds) hold engines that have to
    go, and are not kept."""
    made = []

    class Kept(serve.Driver):
        def __init__(self, loop, seconds, *a, **k):
            super().__init__(loop, seconds, *a, **k)
            if math.isfinite(seconds):
                made.append(self)

    monkeypatch.setattr(serve, "Driver", Kept)
    return made


@pytest.mark.parametrize("cell", [CELL, OLMO])
def test_a_laps_cells_tail_is_over_its_whole_laps_gaps(cell, tmp_path,
                                                       capsys, monkeypatch):
    made = _keep_drivers(monkeypatch)
    assert run.main(["--workload", cell, "--seed", "2147483999",
                     "--seconds", "3", "--trace", "0",
                     "--keep", str(tmp_path)]) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    notes = json.loads((tmp_path / f"{cell}.trace0.seed2147483999.json")
                       .read_text())["notes"]
    drv, = made
    whole = [g for lap in drv.laps if lap.whole for _, g in drv.gaps[lap.gaps]]
    assert line["correct"] is True and notes["gaps_of"] == "whole laps"
    assert line["metrics"]["itl_p99_ms"]["value"] == 1e3 * stats.percentile(
        whole, 99.0)
    assert notes["itl_p50_ms"] == 1e3 * stats.median(whole)
    # every whole lap adds the same gaps, and the cut lap none
    laps = notes["laps"]
    assert laps["whole"] >= 1 and len(laps["gaps"]) == 1
    assert notes["gaps_in_window"] == laps["whole"] * laps["gaps"][0]
    # the lap's gaps are its gaps: every one lies between two of its polls
    assert sum(lap.gaps.stop - lap.gaps.start for lap in drv.laps) == len(
        drv.gaps)
    assert notes["itl_p99_ms_stamped_in_window"] > 0


def test_an_open_loop_counts_every_gap_stamped_inside_the_window(
        monkeypatch):
    import jax

    from chipbench import device

    made = _keep_drivers(monkeypatch)
    cell = spec.load_cell(CELL, True)
    traffic = spec.merge(cell.traffic, {"loop": {
        "kind": "open", "rate_per_s": 4.0, "drain_grace_s": 20.0}})
    out = serve.run(cell._replace(traffic=traffic), 12, 2.0, False,
                    jax.devices()[:1], device.CompileCounter().install(),
                    time.perf_counter())
    drv, = made
    stamped = [g for s, g in drv.gaps if s <= out["window_s"]]
    assert out["counters"]["laps"] is None
    assert out["counters"]["gaps_of"] == "window"
    assert out["counters"]["gaps_in_window"] == len(stamped) > 0
    assert out["values"]["itl_p99_ms"] == 1e3 * stats.percentile(
        stamped, 99.0) == out["counters"]["itl_p99_ms_stamped_in_window"]
    assert out["correct"] is True and not math.isnan(
        out["values"]["itl_p99_ms"])


def test_a_lap_changes_the_tokens_and_nothing_else():
    t = spec.load_json(f"{spec.HERE}/traffic/chat-sat.json")
    a = loadgen.make_requests(t, 5, 1000, 10.0)
    b = loadgen.make_requests(t, 5, 1000, 10.0, lap=1)
    assert a[0].prompt.tolist() == loadgen.make_requests(
        t, 5, 1000, 10.0, lap=0)[0].prompt.tolist()
    assert [(len(x.prompt), x.max_new_tokens) for x in a] == [
        (len(x.prompt), x.max_new_tokens) for x in b]
    assert not np.array_equal(a[0].prompt, b[0].prompt)
    # without a fixed realisation too: the lengths are lap 0's
    free = {k: v for k, v in t.items() if k != "schedule_seed"}
    c = loadgen.make_requests(free, 5, 1000, 10.0)
    d = loadgen.make_requests(free, 5, 1000, 10.0, lap=2)
    assert [len(x.prompt) for x in c] == [len(x.prompt) for x in d]


def test_the_chat_cell_runs_whole_laps_of_the_same_work(tmp_path, capsys):
    assert run.main(["--workload", CELL, "--seed", "8", "--seconds", "3",
                     "--trace", "0", "--keep", str(tmp_path)]) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    kept = json.loads((tmp_path / f"{CELL}.trace0.seed8.json").read_text())
    laps = kept["notes"]["laps"]
    want = spec.load_cell(CELL, True).traffic["loop"]["laps"]["steps"]
    assert laps["whole"] >= 3 and laps["cut"] <= 1
    # the same iterations commit the same tokens, lap after lap
    assert laps["steps"] == [want] and len(laps["tokens"]) == 1
    lo, med, hi = laps["seconds_min_median_max"]
    assert lo <= med <= hi
    # the median of the laps' rates: with an even number of laps it is
    # the mean of two rates, not the rate of the mean of two times
    rate = line["metrics"]["serve_out_tok_per_s"]["value"]
    assert laps["tokens"][0] / hi <= rate <= laps["tokens"][0] / lo
    assert rate == pytest.approx(laps["tokens"][0] / med, rel=0.05)
    # engines were built inside the window and nothing was compiled there
    assert kept["compiles_in_window"] == 0 and line["correct"] is True
    assert kept["notes"]["steps"] >= laps["whole"] * want


def test_an_engine_that_was_let_go_leaves_the_device():
    import jax

    cell = spec.load_cell(CELL, True)
    cfg, dep = cell.config, cell.config["deployment"]
    from chipbench.families import llama

    model, params = llama.build(cfg, 0, dtype=dep["dtype"])
    jax.block_until_ready(params)
    before = serve.live_bytes()
    engine = serve.build_engine(model, params, dep)
    engine.warmup()
    pool = int(engine.blocks.num_blocks) * int(engine.blocks.block_bytes)
    assert serve.live_bytes() - before >= pool
    with pytest.raises(SystemExit, match="still held"):
        serve.check_released(before, pool)
    req = engine.submit(np.arange(3, 20, dtype=np.int32), 4)
    for _ in range(3):
        engine.step()
    jax.block_until_ready(jax.live_arrays())
    del engine
    gc.collect()
    # the request the driver still holds keeps nothing of the pools
    serve.check_released(before, pool)
    assert len(req.output) > 0


def test_a_window_that_holds_no_whole_lap_is_not_correct():
    import jax

    from chipbench import device

    cell = spec.load_cell(CELL, True)
    traffic = spec.merge(cell.traffic,
                         {"loop": {"laps": {"steps": 10 ** 6}}})
    out = serve.run(cell._replace(traffic=traffic), 9, 0.5, False,
                    jax.devices()[:1], device.CompileCounter().install(),
                    time.perf_counter())
    assert out["correct"] is False
    assert out["counters"]["laps"]["whole"] == 0
    assert out["counters"]["check"]["ok"]       # the outputs were right
    assert out["values"]["serve_out_tok_per_s"] > 0
