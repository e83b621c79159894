"""Every cell end to end at tiny size on the CPU (the rehearsal): the
last line is one JSON object with the contract's keys."""

import json

import pytest

from chipbench import run, spec

CELLS = [w["name"] for w in spec.load_benchmark()["workloads"]]
KEYS = {"correct", "attempted", "failed", "metrics", "device"}
DEVICE_KEYS = {"platform", "kind", "count", "memory_peak_bytes",
               "jax_version"}


def _last_line(capsys):
    out = capsys.readouterr().out.strip().splitlines()
    return json.loads(out[-1])


@pytest.mark.parametrize("cell", CELLS)
def test_cell_untraced_prints_its_end_to_end_metrics(cell, capsys):
    assert run.main(["--workload", cell, "--seed", "3", "--seconds", "2",
                     "--trace", "0"]) == 0
    line = _last_line(capsys)
    assert set(line) == KEYS
    bench = spec.load_benchmark()
    want = {m["name"] for m in bench["end_to_end"]
            if "workloads" not in m or cell in m["workloads"]}
    assert set(line["metrics"]) == want and "setup_s" in want
    assert len(want) >= 2
    for name, m in line["metrics"].items():
        assert m["value"] > 0, name
        assert m["unit"] == {e["name"]: e["unit"]
                             for e in bench["end_to_end"]}[name]
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    chips = {w["name"]: w["chips"] for w in bench["workloads"]}[cell]
    import jax

    assert line["device"] == {"platform": "cpu", "kind": "cpu",
                              "count": chips, "memory_peak_bytes": 0,
                              "jax_version": jax.__version__}


@pytest.mark.parametrize("cell", CELLS)
def test_cell_traced_prints_its_per_layer_metrics(cell, capsys):
    assert run.main(["--workload", cell, "--seed", "4", "--seconds", "3",
                     "--trace", "1"]) == 0
    line = _last_line(capsys)
    assert set(line) == KEYS | {"breakdown"}
    assert set(line["device"]) == DEVICE_KEYS | {"busy_s", "window_s"}
    bench = spec.load_benchmark()
    allowed = {m["name"] for m in bench["per_layer"]
               if "workloads" not in m or cell in m["workloads"]}
    assert set(line["metrics"]) <= allowed
    assert line["metrics"]["compiles_in_window"]["value"] == 0
    # both from the trace: the busy union is a part of the traced span
    assert 0 < line["device"]["busy_s"] <= line["device"]["window_s"]
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    for rows in line["breakdown"].values():
        assert len(rows) <= 10
        assert all(isinstance(n, str) and s >= 0 for n, s in rows)
    assert line["correct"] is True


def test_keep_holds_the_line_and_the_runs_notes(tmp_path, capsys):
    cell = "qwen2.5-3b-chat-sat"
    assert run.main(["--workload", cell, "--seed", "5", "--seconds", "2",
                     "--trace", "0", "--keep", str(tmp_path)]) == 0
    line = _last_line(capsys)
    kept = json.loads(
        (tmp_path / f"{cell}.trace0.seed5.json").read_text())
    assert {k: kept[k] for k in line} == line
    assert kept["workload"] == cell and kept["seed"] == 5
    assert kept["compiles_in_window"] == 0
    assert kept["notes"]["check"]["ok"] and kept["notes"]["refused"] == 0
    assert set(kept["end_to_end"]) == set(line["metrics"])


def _open_loop_cell():
    """The Qwen configuration under the test data's open-loop mix of
    sessions over shared documents: not a cell of BENCHMARK.json."""
    import os

    cell = spec.load_cell("qwen2.5-3b-chat-sat", True)
    mix = spec.load_json(os.path.join(os.path.dirname(__file__), "data",
                                      "open-docs.json"))
    return cell._replace(name="open-docs", traffic_name="open-docs",
                         traffic=mix)


@pytest.mark.parametrize("trace", [False, True])
def test_open_loop_sessions_drive_the_engine(trace):
    """Arrivals over the window from the seed, each request timed from
    when it was due, the drain, and the prefix cache at work."""
    import time

    import jax

    from chipbench import device
    from chipbench.kinds import serve

    out = serve.run(_open_loop_cell(), 6, 2.0, trace, jax.devices()[:1],
                    device.CompileCounter().install(), time.perf_counter())
    c = out["counters"]
    assert out["correct"] and out["failed"] == 0
    assert out["attempted"] == c["requests_sent"] == c["requests_finished"]
    assert 20 <= out["attempted"] <= 70          # 20 req/s for 2 s
    assert out["values"]["ttft_p90_ms"] > 0 and c["ttft_p50_ms"] > 0
    assert c["lateness"]["n"] == out["attempted"]
    assert 0 < c["prefix_cached_tokens"] < c["prompt_tokens"]
    if trace:
        assert c["kv_held_blocks_peak"] > 0
        out["session"].stop()


def test_sweep_names_a_knee_for_a_mix_that_is_no_cell_yet(capsys):
    import os

    from chipbench.tools import sweep_rate

    sweep_rate.main("qwen2.5-3b-chat-sat", os.path.join(
        os.path.dirname(__file__), "data", "open-docs.json"), 1.5,
        [5.0, 10.0])
    lines = [json.loads(l) for l in
             capsys.readouterr().out.strip().splitlines()]
    assert [l["rate_per_s"] for l in lines[:2]] == [5.0, 10.0]
    assert all(len(l["in_system_mean_by_third"]) == 3 for l in lines[:2])
    assert set(lines[-1]) == {"knee_per_s", "rate_per_s"}


def test_refuses_to_start_with_a_knob_of_the_program_set(monkeypatch, capsys):
    monkeypatch.setenv("HSTD_SERVE_OVERLAP", "off")
    assert run.main(["--workload", CELLS[0], "--seconds", "1"]) == 2
    assert capsys.readouterr().out == ""


def test_fails_without_a_chip_unless_the_cpu_was_asked_for(monkeypatch):
    from chipbench import device

    assert device.asked_for_cpu()            # conftest names the CPU
    monkeypatch.setattr(device, "asked_for_cpu", lambda: False)
    with pytest.raises(device.NoChipError):
        device.devices_for(1)
    monkeypatch.undo()
    with pytest.raises(device.NoChipError):
        device.devices_for(64)


def test_peaks_table_is_keyed_by_exact_device_kind():
    from chipbench import arith

    v5e = arith.peaks("TPU v5 lite")
    assert v5e["bf16_tflops"] == 197.0 and v5e["hbm_gbytes_per_s"] == 819.0
    with pytest.raises(LookupError):
        arith.peaks("TPU v5")


def test_arithmetic_from_shapes():
    from chipbench import arith

    # BERT-large at 512 tokens: about 1.0 TFLOP a sample
    f = arith.encoder_train_flops_per_sample(1024, 4096, 24, 512)
    assert f == pytest.approx(1.006e12, rel=0.01)
    k = arith.flash_flops_bytes(16, 16, 512, 64)
    assert k["flash_fwd"]["flops"] == 4 * 16 * 16 * 512 * 512 * 64
    assert k["flash_bwd_dkv"]["flops"] == 2 * k["flash_fwd"]["flops"]
    roof = arith.roofline_seconds(k["flash_fwd"]["flops"],
                                  k["flash_fwd"]["bytes"],
                                  arith.peaks("TPU v5 lite"))
    assert roof["bound"] == "compute"
    assert arith.kv_bytes_read_per_step(16, 2048, 36864) == 16 * 2048 * 36864
    assert arith.decode_step_bytes(10, 2, 4, 8) == 10 + 64
