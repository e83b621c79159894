#!/usr/bin/env python
"""Lint telemetry artifacts against the event schema.

Usage::

    python scripts/check_telemetry_schema.py telemetry/events.jsonl \
        telemetry/trace.json
    python scripts/check_telemetry_schema.py <out_dir>/telemetry

``*.jsonl`` paths are validated as event streams, ``*.json`` as Chrome
traces; a directory validates the ``events.jsonl``/``trace.json`` it
contains. Pure stdlib by construction — ``obs.schema`` imports nothing
outside the standard library — so this runs on boxes without jax (CI
lint steps, the bench driver). Exit 0 iff every file parses, every
event carries the envelope + per-type required fields (`host_pause`
and a span's `parent` among them; a serve event's typed-when-present
fields include the iteration ledger's `stage_s` / `dispatch_s` /
`fetch_wait_s` / `commit_s` / `gap_s`; a `program_map`'s rows each name
a scope that exists and a known component), and at least one
valid event exists per file (an empty artifact is a failure: it means
the instrumented run emitted nothing). A torn FINAL jsonl line is
tolerated (crash-safe append contract); torn middle lines are not.
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from huggingface_sagemaker_tensorflow_distributed_tpu.obs.schema import (  # noqa: E402
    validate_events_file,
    validate_trace_file,
)


def expand(paths: list[str]) -> list[str]:
    out = []
    for p in paths:
        if os.path.isdir(p):
            found = [os.path.join(p, n) for n in sorted(os.listdir(p))
                     if n in ("events.jsonl", "trace.json")
                     or (n.endswith(".jsonl")
                         and n.startswith(("events.host", "flight_")))]
            if not found:
                out.append(os.path.join(p, "events.jsonl"))  # report missing
            out.extend(found)
        else:
            out.append(p)
    return out


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("paths", nargs="+",
                        help="events.jsonl / trace.json files or a "
                             "telemetry directory")
    parser.add_argument("--strict-tail", action="store_true",
                        help="reject a torn final jsonl line too")
    parser.add_argument("-q", "--quiet", action="store_true")
    args = parser.parse_args(argv)

    failed = False
    for path in expand(args.paths):
        if not os.path.exists(path):
            print(f"FAIL {path}: missing")
            failed = True
            continue
        if path.endswith(".jsonl"):
            count, errors = validate_events_file(
                path, strict_tail=args.strict_tail)
            kind = "events"
        else:
            count, errors = validate_trace_file(path)
            kind = "trace events"
        if count == 0 and not errors:
            errors = ["no valid events (empty artifact)"]
        if errors:
            failed = True
            print(f"FAIL {path}: {count} valid {kind}, "
                  f"{len(errors)} error(s)")
            for e in errors[:20]:
                print(f"  {e}")
            if len(errors) > 20:
                print(f"  ... and {len(errors) - 20} more")
        elif not args.quiet:
            print(f"OK   {path}: {count} valid {kind}")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
