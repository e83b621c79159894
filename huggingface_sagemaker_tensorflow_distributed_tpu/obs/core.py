"""Telemetry core: the process-wide state, the crash-safe JSONL event
log, the span tracer, and the scalar metrics sink.

Design constraints (ISSUE 1 acceptance):

- ``HSTD_TELEMETRY=0`` must cost exactly zero allocations on the trainer
  hot loop: every public entry point early-returns on a cached bool, and
  the disabled ``span()`` returns one shared singleton context manager.
- Enabled-but-unconfigured (no output dir) runs record nothing but
  the LIFE-CYCLE spans (``lifecycle_span``: warm-up and the like, at
  most ``_MAX_LIFECYCLE_SPANS``), which wait in memory for a later
  ``configure`` — unit tests stay clean.
- File emission is append + flush per line, so a SIGKILL tears at most
  the final line (``schema.iter_events`` skips a torn tail); fsync runs
  every ``_FSYNC_EVERY`` lines to bound data loss on power-cut-class
  failures without paying fsync latency per event. Spans are the
  exception: they are kept in memory and written in one batch at
  ``flush_spans`` (``obs.flush`` / ``shutdown`` / every heartbeat, and
  whenever ``_SPAN_BATCH`` are pending), so a loop that opens a dozen
  spans an iteration pays no write inside it; a kill loses the spans
  since the last batch.
- A span is also a ``jax.profiler.TraceAnnotation`` (``hstd/<name>``)
  when jax is ALREADY imported, so the program's spans lie on the
  device trace's clock in any profiled run. No jax import anywhere in
  this module (``sys.modules`` only): the host/rank id comes from the
  launcher env contract (``TPU_PROCESS_ID``) or an explicit
  ``set_host`` call from ``parallel.distributed``.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time
from typing import Optional

from huggingface_sagemaker_tensorflow_distributed_tpu.obs.flight import (
    FlightRecorder,
)
from huggingface_sagemaker_tensorflow_distributed_tpu.obs.schema import (
    SCHEMA_VERSION,
)

ENV_ENABLE = "HSTD_TELEMETRY"
ENV_DIR = "HSTD_TELEMETRY_DIR"
ENV_HEARTBEAT = "HSTD_HEARTBEAT_SECS"
# every host writes its own event file (events.host<K>.jsonl; host 0
# keeps events.jsonl) — per-host FILES, so shared-filesystem runs never
# interleave appends into one file. Off by default: rank-0-only is the
# PR 1 discipline, this is the opt-in that makes `obsctl report` a real
# N-host merge.
ENV_ALL_HOSTS = "HSTD_TELEMETRY_ALL_HOSTS"

_FSYNC_EVERY = 64
_MAX_BUFFERED_SPANS = 200_000
_SPAN_BATCH = 10_000          # pending span records that force a write
_MAX_LIFECYCLE_SPANS = 256    # kept while no sink exists yet
ANNOTATION_PREFIX = "hstd/"   # a span's name in the profiler's trace


def _env_enabled() -> bool:
    return os.environ.get(ENV_ENABLE, "1").strip().lower() not in (
        "0", "false", "off", "no")


def _all_hosts_env() -> bool:
    return os.environ.get(ENV_ALL_HOSTS, "").strip().lower() in (
        "1", "true", "on", "yes")


def event_filename(host: int) -> str:
    """Per-host event file name: host 0 keeps the historical
    ``events.jsonl``; other hosts (under ``HSTD_TELEMETRY_ALL_HOSTS``)
    get unique names so shared-filesystem appends never interleave."""
    return "events.jsonl" if host == 0 else f"events.host{host}.jsonl"


class EventLog:
    """Append-only JSONL writer with the envelope fields stamped on.

    The file opens lazily at the FIRST emit (with ``header`` written
    ahead of it) — so merely constructing the log, e.g. on a host whose
    rank is still an import-time guess, never touches a shared
    filesystem; a later ``set_host`` demotion closes the unused log
    before any line lands.
    """

    def __init__(self, path: str, host: int,
                 header: Optional[tuple[str, dict]] = None,
                 ring: Optional[FlightRecorder] = None):
        self.path = path
        self.host = host
        self.ring = ring
        self._header = header
        self._lock = threading.Lock()
        self._file = None
        self._since_fsync = 0

    def stamp_record(self, etype: str, fields: dict) -> dict:
        record = {"v": SCHEMA_VERSION, "t": time.time(), "host": self.host,
                  "pid": os.getpid(), "type": etype}
        record.update(fields)
        return record

    def _stamp(self, etype: str, fields: dict) -> str:
        return json.dumps(self.stamp_record(etype, fields),
                          default=str) + "\n"

    def emit(self, etype: str, fields: dict) -> None:
        self.emit_many(etype, (fields,))

    def emit_many(self, etype: str, rows) -> None:
        """One event of ``etype`` per element of ``rows``, all stamped
        now and written with ONE write + flush."""
        lines = []
        for fields in rows:
            record = self.stamp_record(etype, fields)
            if self.ring is not None:
                # flight recorder (obs/flight.py): every written event
                # also lands in the bounded ring an anomaly dump
                # snapshots
                self.ring.record(record)
            lines.append(json.dumps(record, default=str) + "\n")
        if not lines:
            return
        with self._lock:
            if self._file is None:
                os.makedirs(os.path.dirname(self.path) or ".", exist_ok=True)
                self._file = open(self.path, "a", encoding="utf-8")
                if self._header is not None:
                    hdr_type, hdr_fields = self._header
                    self._header = None
                    self._file.write(self._stamp(hdr_type, hdr_fields))
            self._file.write("".join(lines))
            self._file.flush()
            self._since_fsync += len(lines)
            if self._since_fsync >= _FSYNC_EVERY:
                os.fsync(self._file.fileno())
                self._since_fsync = 0

    def close(self) -> None:
        with self._lock:
            if self._file is not None:
                self._file.flush()
                os.fsync(self._file.fileno())
                self._file.close()
                self._file = None


class ObsState:
    """One per process: configuration + span buffer + file sinks."""

    def __init__(self):
        self.enabled = _env_enabled()
        self.host = int(os.environ.get("TPU_PROCESS_ID", "0") or 0)
        self.host_count = int(os.environ.get("TPU_NUM_PROCESSES", "1") or 1)
        self.dir: Optional[str] = None
        self.events: Optional[EventLog] = None
        self.mono0 = time.perf_counter()
        # span records: (name, mono_start, dur, tid, depth, parent, args)
        self.spans: list = []          # all of them, for trace.json
        self.spans_dropped = 0
        self._pending: list = []       # not yet written to events.jsonl
        self._lifecycle: list = []     # recorded before any sink existed
        # flight recorder (obs/flight.py): bounded ring of recent event
        # records, dumped by the anomaly detector at an incident.
        # HSTD_FLIGHT_RING=0 disables it.
        self.ring: Optional[FlightRecorder] = FlightRecorder.from_env()
        self._tl = threading.local()
        self._lock = threading.Lock()
        self._span_lock = threading.Lock()
        env_dir = os.environ.get(ENV_DIR, "").strip()
        if self.enabled and env_dir:
            self._open_dir(env_dir)

    # -- configuration ------------------------------------------------------

    def _open_dir(self, path: str) -> None:
        self.dir = path
        # multi-host runs on a shared filesystem: by default only host 0
        # owns the files (interleaved appends from many writers would
        # tear lines); HSTD_TELEMETRY_ALL_HOSTS=1 gives every host its
        # OWN file (event_filename) so a cross-host `obsctl report`
        # merge is possible without any append interleaving. The "run"
        # header is written lazily with the first real event: a host
        # whose rank is an env guess (auto-detected pods) never touches
        # a file before initialize_distributed corrects it via set_host.
        if self.host == 0 or _all_hosts_env():
            self._open_event_log()

    def _open_event_log(self) -> None:
        header = ("run", {"argv": sys.argv,
                          "python": sys.version.split()[0]}) \
            if self.host == 0 else None
        self.events = EventLog(
            os.path.join(self.dir, event_filename(self.host)), self.host,
            header=header, ring=self.ring)
        # life-cycle spans that ended before this log existed are
        # replayed into it under their own stamps
        with self._span_lock:
            replay, self._lifecycle = self._lifecycle, []
            self.spans.extend(replay)
            self._pending.extend(replay)

    def configure(self, out_dir: Optional[str] = None,
                  enabled: Optional[bool] = None) -> None:
        with self._lock:
            if enabled is not None:
                self.enabled = enabled
            if out_dir and self.enabled and self.dir != out_dir:
                if self.events is not None:
                    self.flush_spans()
                    self.events.close()
                    self.events = None
                self._open_dir(out_dir)

    def set_host(self, index: int, count: int) -> None:
        changed = index != self.host
        self.host = index
        self.host_count = count
        if not changed:
            return
        if self.events is not None:
            # the rank guess was wrong: close the unused log (lazy open
            # means no file was touched) and reopen under the real rank
            self.events.close()
            self.events = None
        if (self.dir is not None and self.enabled
                and (index == 0 or _all_hosts_env())):
            self._open_event_log()

    # -- span recording -----------------------------------------------------

    def add_span(self, name: str, mono_start: float, dur: float,
                 args: Optional[dict], depth: int = 0,
                 parent: Optional[str] = None,
                 lifecycle: bool = False) -> None:
        """Keep one finished span in memory. With a sink it waits for
        the next :meth:`flush_spans`; without one only a ``lifecycle``
        span is kept, for the replay when a sink opens."""
        tid = threading.get_ident() & 0x7FFFFFFF
        record = (name, mono_start, dur, tid, depth, parent, args)
        with self._span_lock:
            if self.events is None:
                if lifecycle and len(self._lifecycle) < _MAX_LIFECYCLE_SPANS:
                    self._lifecycle.append(record)
                return
            if len(self.spans) < _MAX_BUFFERED_SPANS:
                self.spans.append(record)
            else:
                self.spans_dropped += 1
            self._pending.append(record)
            full = len(self._pending) >= _SPAN_BATCH
        if full:
            self.flush_spans()

    def flush_spans(self) -> int:
        """Write the pending span records to ``events.jsonl`` in one
        batch; returns how many. The envelope ``t`` of a span is this
        moment: a span's own times are ``mono`` and ``dur``."""
        events = self.events
        if events is None:
            return 0
        with self._span_lock:
            pending, self._pending = self._pending, []
        rows = []
        for name, mono, dur, tid, depth, parent, args in pending:
            fields = {"name": name, "dur": round(dur, 9),
                      "mono": round(mono, 9), "tid": tid, "depth": depth}
            if parent is not None:
                fields["parent"] = parent
            if args:
                fields["args"] = args
            rows.append(fields)
        events.emit_many("span", rows)
        return len(rows)

    # -- trace.json projection ----------------------------------------------

    def flush_trace(self, path: Optional[str] = None) -> Optional[str]:
        """Write the Chrome-trace projection of the buffered spans
        atomically (tmp + rename), so a concurrent kill never leaves a
        half-written trace.json. Returns the path written, or None."""
        if path is None:
            if self.dir is None or self.host != 0:
                return None
            path = os.path.join(self.dir, "trace.json")
        events = [
            {"name": name, "ph": "X", "ts": round(mono * 1e6, 3),
             "dur": round(dur * 1e6, 3), "pid": self.host, "tid": tid}
            for name, mono, dur, tid, *_rest in list(self.spans)
        ]
        doc = {"traceEvents": events, "displayTimeUnit": "ms",
               "otherData": {"schema_version": SCHEMA_VERSION,
                             "spans_dropped": self.spans_dropped}}
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w", encoding="utf-8") as f:
            json.dump(doc, f)
        os.replace(tmp, path)
        return path

    def shutdown(self) -> None:
        self.flush_trace()
        if self.events is not None:
            self.flush_spans()
            self.events.close()
            self.events = None


class _NullSpan:
    """The disabled-path span: ONE shared instance, allocation-free."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


NULL_SPAN = _NullSpan()


def _annotation(name: str):
    """A ``jax.profiler.TraceAnnotation`` for ``name``, or None while
    jax has not been imported: this module never imports it."""
    jax = sys.modules.get("jax")
    profiler = getattr(jax, "profiler", None)
    cls = getattr(profiler, "TraceAnnotation", None)
    return None if cls is None else cls(ANNOTATION_PREFIX + name)


class _Span:
    __slots__ = ("_state", "_name", "_args", "_lifecycle", "_t0", "_ann")

    def __init__(self, state: ObsState, name: str, args: Optional[dict],
                 lifecycle: bool = False):
        self._state = state
        self._name = name
        self._args = args
        self._lifecycle = lifecycle

    def __enter__(self):
        tl = self._state._tl
        stack = getattr(tl, "stack", None)
        if stack is None:
            stack = tl.stack = []
        stack.append(self._name)
        self._ann = _annotation(self._name)
        if self._ann is not None:
            self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dur = time.perf_counter() - self._t0
        if self._ann is not None:
            self._ann.__exit__(*exc)
        stack = self._state._tl.stack
        stack.pop()
        self._state.add_span(self._name, self._t0 - self._state.mono0, dur,
                             self._args, depth=len(stack),
                             parent=stack[-1] if stack else None,
                             lifecycle=self._lifecycle)
        return False


class Tracer:
    """Nestable wall-time spans; ``span()`` is the only hot-path entry.

    Recording requires an output dir (``configure``/``HSTD_TELEMETRY_DIR``)
    — an un-instrumented process gets the shared no-op singleton, paying
    neither per-span allocation nor the unreadable-by-anything span
    buffer growing toward its cap."""

    def __init__(self, state: ObsState):
        self._state = state

    def span(self, name: str, args: Optional[dict] = None):
        state = self._state
        if not state.enabled or state.dir is None:
            return NULL_SPAN
        return _Span(state, name, args)

    def lifecycle_span(self, name: str, args: Optional[dict] = None):
        """A span of the process's life cycle (warm-up, a program's
        first run): recorded whether or not a directory is configured
        yet, and replayed under its own stamps when one is. For what
        happens a few times a process; never inside a step loop (the
        buffer holds ``_MAX_LIFECYCLE_SPANS``)."""
        if not self._state.enabled:
            return NULL_SPAN
        return _Span(self._state, name, args, lifecycle=True)


class MetricsSink:
    """Rank-0 scalar series → events.jsonl ``metric`` lines.

    Calls are positional on the hot path (no kwargs dict churn); when
    telemetry is disabled or no file sink is configured, ``scalar`` is a
    two-comparison early return.
    """

    def __init__(self, state: ObsState):
        self._state = state

    def scalar(self, name: str, value, step: Optional[int] = None,
               args: Optional[dict] = None) -> None:
        state = self._state
        if not state.enabled or state.events is None:
            return
        fields: dict = {"name": name,
                        "value": None if value is None else float(value)}
        if step is not None:
            fields["step"] = int(step)
        if args:
            fields["args"] = args
        state.events.emit("metric", fields)
