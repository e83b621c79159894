"""``chip_smoke.py``: the rehearsal walks every leg on the CPU at tiny
widths; without a TPU and without the rehearsal flag it fails and
prints no result; one failed leg fails the run."""

import json
import os
import subprocess
import sys

import pytest

import chip_smoke

ROOT = chip_smoke.ROOT


def _ok_line(out: str):
    lines = [ln for ln in out.strip().splitlines() if ln.startswith("{")]
    last = json.loads(lines[-1]) if lines else {}
    return last if last.get("ok") is True and "device" in last else None


def test_rehearsal_runs_every_leg_and_artefact_checks_hold(
        tmp_path, monkeypatch, capsys):
    monkeypatch.setattr(chip_smoke, "OUT", str(tmp_path))
    # one CPU device, as on one chip (the suite's own mesh has eight)
    monkeypatch.delenv("XLA_FLAGS", raising=False)
    assert chip_smoke.main(["--rehearse-cpu"]) == 0
    out = capsys.readouterr().out
    assert _ok_line(out) == {
        "ok": True, "rehearsal": True,
        "device": {"platform": "cpu", "kind": "cpu", "count": 1}}
    legs = {r["leg"]: r for r in map(json.loads, out.strip().splitlines())
            if "leg" in r}
    assert list(legs) == ["fine-tune", "causal-lm", "serve", "serve-pallas",
                          "oracle"]
    assert all(r["ok"] for r in legs.values())
    assert legs["fine-tune"]["steps"] == 10
    assert legs["serve"]["compiles_after_warmup"] == 0
    assert legs["serve-pallas"]["kernel"] == "pallas"
    assert legs["oracle"]["against"] == ["serve", "serve-pallas"]
    assert legs["oracle"]["compared"] == 4
    for leg in ("fine-tune", "causal-lm"):
        for artefact in ("output/train_results.txt",
                         "output/eval_results.txt",
                         "model/model.safetensors", "model/config.json"):
            assert (tmp_path / leg / artefact).exists()


def test_cpu_asked_by_name_without_the_flag_fails_at_once():
    """The sandbox case: JAX_PLATFORMS=cpu, no rehearsal flag. No leg
    starts, nothing is printed on stdout."""
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=ROOT, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, timeout=60)
    assert proc.returncode != 0
    assert proc.stdout == ""


def test_without_a_tpu_and_without_cpu_named_every_entry_point_fails():
    """libtpu fails to initialize here and jax falls back to the CPU
    with a warning; the smoke (through ``scripts/train.py``) and the
    server must not carry on. One process each, side by side: no chip
    to contend for here."""
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "XLA_FLAGS")}
    procs = {name: subprocess.Popen(
        [sys.executable, *argv], cwd=ROOT, env=env, text=True,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE)
        for name, argv in (
            ("smoke", ["chip_smoke.py"]),
            ("serve", [os.path.join("scripts", "serve.py"),
                       "--requests", "1"]))}
    done = {name: (*p.communicate(timeout=300), p.returncode)
            for name, p in procs.items()}
    for name, (out, err, code) in done.items():
        assert code != 0, name
        assert _ok_line(out) is None, name
    assert "NoAcceleratorError" in done["smoke"][1]
    assert "NoAcceleratorError" in done["serve"][1]
    assert not [ln for ln in done["serve"][0].splitlines()
                if ln.startswith("{")]        # no request row, no summary


def test_outside_a_checkout_it_fails(tmp_path):
    (tmp_path / "chip_smoke.py").write_text(
        open(os.path.join(ROOT, "chip_smoke.py")).read())
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                          text=True, capture_output=True, timeout=60)
    assert proc.returncode != 0
    assert proc.stdout == ""


@pytest.mark.parametrize("failing", [None, "causal-lm", "oracle"])
def test_one_failed_leg_fails_the_whole_run(monkeypatch, capsys, failing):
    """The aggregation alone, legs stubbed: all pass → exit 0 and the
    result line; any one fails → non-zero and no result line."""
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}

    def stub(name, sizes, rehearse, *args):
        if name == failing:
            raise chip_smoke.LegFailed("forced")
        return {"leg": name, "ok": True, **device, "model_dir": "m",
                "outputs": {}, "requests_path": "r", "rows_path": "o",
                "tp": 1, "kv_pool_bytes_per_device": 1,
                "param_bytes_per_device": 1}

    for leg in ("train_leg", "serve_leg", "oracle_leg"):
        monkeypatch.setattr(chip_smoke, leg, stub)
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    code = chip_smoke.main([])
    out = capsys.readouterr().out
    if failing is None:
        assert code == 0
        assert json.loads(out.strip().splitlines()[-1]) == {
            "ok": True, "device": device}
    else:
        assert code != 0
        assert _ok_line(out) is None
