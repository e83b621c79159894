"""A closed loop run as laps: every whole lap is the same work, the rate
is the median lap's, and a lap's engine is gone before the next one."""

import gc
import json
import time

import numpy as np
import pytest

from chipbench import loadgen, run, spec
from chipbench.kinds import serve
from chipbench.kinds.serve import Lap, lap_rate

CELL = "qwen2.5-3b-chat-sat"


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
