"""Run one serving cell traced, as ``chipbench.run`` does, and check the
engine's own account of its iterations on the events the run read.

    python3 -m chipbench.tools.host_loop_ledger --workload <cell> \\
        --seed <n> [--seconds <s>] [--keep <dir>]

The benchmark deletes a run's telemetry once its readers have seen it;
this keeps the events of the same run (it is ``chipbench.run`` with one
function wrapped) and prints, after the run's own line, one JSON line:
whether ``dur_s >= stage_s + dispatch_s + fetch_wait_s + commit_s`` and
``gap_s >= 0`` hold on every ``iteration_ledger`` line, how much of the
summed ``dur_s`` the four parts account for, the host's part of an
iteration, the warm-up span with its children, and the host pauses.
A hand tool: the driver reads nothing of it.
"""

from __future__ import annotations

import json
import sys

from chipbench import device, run, stats

PARTS = ("stage_s", "dispatch_s", "fetch_wait_s", "commit_s")
SLACK_S = 5e-6      # every field of a line is rounded to a microsecond


def check(events: list) -> dict:
    lines = [e for e in events if e.get("type") == "serve"
             and e.get("event") == "iteration_ledger"
             and e.get("fetch_wait_s") is not None]
    broken = [e["iteration"] for e in lines
              if sum(e[p] for p in PARTS) > e["dur_s"] + SLACK_S
              or e["gap_s"] < 0]
    sums = {k: sum(e[k] for e in lines) for k in PARTS + ("dur_s", "gap_s")}
    host = [e["dur_s"] - e["fetch_wait_s"] for e in lines
            if e.get("decode_slots")]
    spans = [e for e in events if e.get("type") == "span"]
    pauses = sorted((e for e in events if e.get("type") == "host_pause"),
                    key=lambda e: -e["dur"])
    worst = max(lines, key=lambda e: e["fetch_wait_s"], default=None)
    return {
        "ledger_lines": len(lines), "identity_broken_at": broken[:8],
        "sums_s": sums,
        "accounted_share": (sum(sums[p] for p in PARTS) / sums["dur_s"]
                            if sums.get("dur_s") else None),
        "host_ms_per_decoding_step_p50_p90_max": [
            1e3 * v for v in (stats.median(host) or 0.0,
                              stats.percentile(host, 90.0) or 0.0,
                              max(host, default=0.0))],
        "gap_ms_p50_p99_max": [
            1e3 * v for v in (
                stats.median([e["gap_s"] for e in lines]) or 0.0,
                stats.percentile([e["gap_s"] for e in lines], 99.0) or 0.0,
                max((e["gap_s"] for e in lines), default=0.0))],
        "fetch_wait_max": (None if worst is None else
                           {k: worst.get(k) for k in
                            ("iteration", "fetch_wait_s", "dur_s",
                             "prefill_dispatches", "decode_slots")}),
        "warmup_spans_s": {e["name"]: e["dur"] for e in spans
                           if e["name"].startswith("serve/warmup")},
        "span_counts": {n: sum(1 for e in spans if e["name"] == n)
                        for n in sorted({e["name"] for e in spans})},
        "host_pauses": len(pauses),
        "host_pauses_longest": [[e["mono"], e["dur"]] for e in pauses[:8]],
    }


def main(argv=None) -> int:
    captured: list = []
    read = device.read_events

    def keep_events(out_dir: str) -> list:
        events = read(out_dir)
        captured.extend(events)
        return events

    device.read_events = keep_events
    try:
        rc = run.main(list(sys.argv[1:] if argv is None else argv)
                      + ["--trace", "1"])
    finally:
        device.read_events = read
    print(json.dumps(check(captured)), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
