"""Supervisor-layer tests for ``bench.py`` (no JAX backend, no real child
process). The contract they pin: the parent never touches JAX; ONE
measured child is the first and only process to open the backend and
names the device itself; its lines are forwarded as they arrive, every
metric line stamped with that device; the kernel-parity subset runs
strictly after the child has exited; and a run with no value to print —
no TPU, child crash, child hang, failed parity — prints a structured
error line and exits non-zero.
"""

import argparse
import json
import subprocess
import sys
import types

import bench

HEADLINE = "bert_base_finetune_samples_per_sec_per_chip"
TPU = {"platform": "tpu", "device_kind": "TPU v5 lite", "device_count": 1,
       "jax_version": "0.9.0"}
_DEFAULT_PARITY = {"pass": 8, "fail": 0, "subset": True, "rc": 0}


def _args(**kw):
    base = dict(model=None, buckets=False, mesh=False, generate=False,
                causal_lm=False, mlm=False, lora=False, banded=False,
                llama_train=False, mixtral_train=False, batch=None,
                opt_state_bf16=False, remat_policy=None,
                budget_seconds=None)
    base.update(kw)
    ns = argparse.Namespace(**base)
    setattr(ns, "_child", False)
    return ns


def _device_line(device=TPU):
    return json.dumps({"metric": HEADLINE, "value": None,
                       "provisional": True, "stage": "device",
                       "device": device})


def _metric_line(value=277.4, metric=HEADLINE, **extra):
    return json.dumps({"metric": metric, "value": value,
                       "unit": "samples/sec/chip", "vs_baseline": 8.669,
                       **extra})


class FakeChild:
    """Stands in for the measured child: a line iterator for stdout, an
    exit code, and a log of the order things happened in."""

    def __init__(self, lines, rc=0, log=None):
        self.stdout = iter(ln + "\n" for ln in lines)
        self.rc, self.log = rc, log if log is not None else []
        self.killed = False

    def wait(self):
        self.log.append("child_exited")
        return -9 if self.killed else self.rc

    def kill(self):
        self.killed = True


def _run(monkeypatch, capsys, args, lines, rc=0, parity=_DEFAULT_PARITY,
         log=None, timeout_fires=False):
    log = log if log is not None else []
    seen = {"popen": 0}

    def fake_popen(argv, **kw):
        seen["popen"] += 1
        seen["argv"], seen["env"] = argv, kw.get("env", {})
        return FakeChild(lines, rc=rc, log=log)

    class FakeTimer:
        def __init__(self, interval, fn):
            seen["timeout"] = interval
            self.fn = fn

        def start(self):
            if timeout_fires:
                self.fn()

        def cancel(self):
            pass

    def fake_parity():
        log.append("parity")
        if parity is None:
            raise AssertionError("parity must not run here")
        return parity

    monkeypatch.setattr(bench.subprocess, "Popen", fake_popen)
    monkeypatch.setattr(bench.threading, "Timer", FakeTimer)
    monkeypatch.setattr(bench, "run_kernel_parity", fake_parity)
    code = bench.supervise(args)
    out = capsys.readouterr().out.strip().splitlines()
    return code, out, seen


def _data(lines):
    """Non-provisional JSON lines: the measurement contract."""
    recs = [json.loads(ln) for ln in lines if ln.startswith("{")]
    return [r for r in recs if not r.get("provisional")]


def test_parent_process_stays_off_jax():
    """Importing bench (what the supervisor parent does) must not
    import jax, let alone open a backend: a parent that has touched
    JAX holds the chip and the child that needs it fails or hangs."""
    code = ("import sys, bench; "
            "sys.exit(1 if 'jax' in sys.modules else 0)")
    proc = subprocess.run([sys.executable, "-c", code],
                          cwd=bench._REPO_ROOT, timeout=60)
    assert proc.returncode == 0
    assert not hasattr(bench, "probe_backend")


def test_one_child_holds_the_chip_and_lines_carry_its_device(
        monkeypatch, capsys):
    code, lines, seen = _run(
        monkeypatch, capsys, _args(generate=True),
        [_device_line(), "note line",
         _metric_line(900.0, "generate_gpt2_greedy_tokens_per_sec_per_chip"),
         _metric_line(50.0, "generate_bart_beam4_tokens_per_sec_per_chip")],
        parity=None)
    assert code == 0
    assert seen["popen"] == 1 and seen["argv"][-1] == "--_child"
    first = json.loads(lines[0])
    assert first["provisional"] is True and first["stage"] == "measuring"
    assert "note line" in lines          # non-JSON lines pass through
    data = _data(lines)
    assert [r["value"] for r in data] == [900.0, 50.0]
    for rec in data:
        assert rec["platform"] == "tpu"
        assert rec["device_kind"] == "TPU v5 lite"
        assert rec["device_count"] == 1


def test_no_tpu_is_an_error_line_and_a_nonzero_exit(monkeypatch, capsys):
    """The child found no TPU (and the CPU was not asked for by name):
    it says so in its own error line; the supervisor adds no second one
    and exits non-zero."""
    err = json.dumps({"metric": HEADLINE, "value": None, "unit": None,
                      "vs_baseline": None, "error": "backend_unreachable",
                      "detail": {"message": "default JAX backend is 'cpu'"}})
    code, lines, _ = _run(monkeypatch, capsys, _args(), [err], rc=1,
                          parity=None)
    assert code == 1
    data = _data(lines)
    assert len(data) == 1 and data[0]["error"] == "backend_unreachable"
    assert data[0]["value"] is None


def test_child_crash_emits_error_line_and_exits_nonzero(monkeypatch, capsys):
    code, lines, _ = _run(monkeypatch, capsys, _args(),
                          [_device_line(), "Traceback (most recent ..."],
                          rc=1, parity=None)
    assert code == 1
    tail = json.loads(lines[-1])
    assert tail["error"] == "bench_failed" and tail["value"] is None
    assert tail["detail"]["rc"] == 1
    assert tail["detail"]["device"]["platform"] == "tpu"


def test_child_timeout_keeps_forwarded_lines_and_exits_nonzero(
        monkeypatch, capsys):
    """A child killed at its deadline may have printed complete metric
    lines already — they were forwarded as they arrived and stay ahead
    of the error line (partial results beat no results)."""
    done = _metric_line(900.0, "generate_gpt2_greedy_tokens_per_sec_per_chip")
    code, lines, _ = _run(monkeypatch, capsys,
                          _args(generate=True, budget_seconds=60),
                          [_device_line(), done, "half a li"],
                          timeout_fires=True, parity=None)
    assert code == 1
    assert _data(lines)[0]["value"] == 900.0
    tail = json.loads(lines[-1])
    assert tail["error"] == "bench_timeout"
    assert tail["metric"].startswith("generate_")


def test_nan_loss_exit_code_propagates(monkeypatch, capsys):
    code, lines, _ = _run(monkeypatch, capsys, _args(),
                          [_device_line(), _metric_line(anomalies=1)],
                          rc=bench.ANOMALY_RC, parity=None)
    assert code == bench.ANOMALY_RC
    assert _data(lines)[-1]["value"] == 277.4


def test_parity_runs_strictly_after_the_child_has_exited(
        monkeypatch, capsys):
    log = []
    code, lines, _ = _run(monkeypatch, capsys, _args(),
                          [_device_line(), _metric_line()], log=log)
    assert code == 0
    assert log == ["child_exited", "parity"]
    data = _data(lines)
    # the headline is on stdout before parity starts, and again — as
    # the last line — with the parity field
    assert [r["value"] for r in data] == [277.4, 277.4]
    assert "kernel_parity" not in data[0]
    assert data[-1]["kernel_parity"] == _DEFAULT_PARITY
    assert data[-1]["platform"] == "tpu"


def test_failed_parity_subset_exits_nonzero_after_printing(
        monkeypatch, capsys):
    bad = {"pass": 7, "fail": 1, "subset": True, "rc": 1,
           "failed": ["flash bwd dq (causal)"]}
    code, lines, _ = _run(monkeypatch, capsys, _args(),
                          [_device_line(), _metric_line()], parity=bad)
    assert code == 1
    assert _data(lines)[-1]["kernel_parity"]["failed"] == [
        "flash bwd dq (causal)"]
    crashed = {"pass": 0, "fail": 0, "subset": True, "rc": 1,
               "error": "crashed", "tail": "..."}
    code, _, _ = _run(monkeypatch, capsys, _args(),
                      [_device_line(), _metric_line()], parity=crashed)
    assert code == 1


def test_parity_skipped_for_sweeps_cpu_runs_and_tight_budgets(
        monkeypatch, capsys):
    """--batch/--opt-state-bf16 runs must NOT pay the parity subset; a
    run on the CPU (asked for by name) has no Mosaic to give evidence
    of; and a budget that cannot fit the subset skips it."""
    cpu = {"platform": "cpu", "device_kind": "cpu", "device_count": 1}
    for args, device in ((_args(batch=64), TPU), (_args(), cpu),
                         (_args(budget_seconds=90), TPU)):
        code, lines, _ = _run(monkeypatch, capsys, args,
                              [_device_line(device), _metric_line(250.0)],
                              parity=None)
        assert code == 0
        data = _data(lines)
        assert len(data) == 1 and "kernel_parity" not in data[0]


def test_budget_caps_child_timeout(monkeypatch, capsys):
    """With --budget-seconds the child deadline derives from the budget
    (not the 30-min default)."""
    _, _, seen = _run(monkeypatch, capsys, _args(budget_seconds=90),
                      [_device_line(), _metric_line()], parity=None)
    assert seen["timeout"] <= 90 + 11
    assert float(seen["env"]["_BENCH_CHILD_BUDGET"]) <= 90
    _, _, seen = _run(monkeypatch, capsys, _args(batch=8),
                      [_device_line(), _metric_line()], parity=None)
    assert seen["timeout"] == bench.CHILD_TIMEOUT_S


def test_install_child_budget_arms_alarm(monkeypatch):
    """The child-side deadline: SIGALRM/SIGTERM handlers installed and
    the alarm leads the budget by the 5s grace."""
    import signal as _signal

    armed = {}
    monkeypatch.setattr(_signal, "signal",
                        lambda sig, fn: armed.setdefault(sig, fn))
    monkeypatch.setattr(_signal, "alarm",
                        lambda s: armed.setdefault("alarm", s))
    monkeypatch.setenv("_BENCH_CHILD_BUDGET", "60")
    bench._install_child_budget(_args(budget_seconds=90))
    assert armed["alarm"] == 55
    assert _signal.SIGTERM in armed
    assert callable(armed[_signal.SIGTERM])


def test_parity_line_parser(monkeypatch):
    """run_kernel_parity's PASS/FAIL accounting against canned output."""
    fake = types.SimpleNamespace(
        returncode=1,
        stdout=("backend: tpu (TPU v5 lite)\n"
                "PASS flash fwd (causal): ...\n"
                "FAIL flash bwd dq (causal): ...\n"
                "PASS vocab-ce loss (gpt2-vocab): ...\n"))
    monkeypatch.setattr(bench.subprocess, "run", lambda *a, **k: fake)
    summary = bench.run_kernel_parity()
    assert summary["pass"] == 2 and summary["fail"] == 1
    assert summary["failed"] == ["flash bwd dq (causal)"]
