"""``obs``: in-repo, dependency-free telemetry.

One process-wide :class:`~.core.ObsState` backs a module-level API so
call sites never thread a tracer through ten layers::

    from huggingface_sagemaker_tensorflow_distributed_tpu import obs

    with obs.span("train/step"):
        ...
    obs.scalar("train/loss", 0.31, step=120)
    obs.heartbeat().start(); obs.pulse()        # liveness + stall dumps

Environment contract (documented in README "Observability"):

- ``HSTD_TELEMETRY=0`` disables everything (zero hot-loop allocations:
  ``span`` returns a shared singleton, ``scalar``/``pulse`` early-return).
- ``HSTD_TELEMETRY_DIR=<dir>`` writes ``events.jsonl`` (streamed,
  crash-safe append) and ``trace.json`` (Chrome trace viewer / Perfetto,
  atomically replaced) into ``<dir>``. Unset → spans/metrics are no-ops
  (the instrumentation is opt-in per run); no files, threads or span
  buffers accumulate in un-instrumented processes (a few life-cycle
  spans, :func:`lifecycle_span`, wait for a later ``configure``).
- ``HSTD_HEARTBEAT_SECS`` sets the liveness cadence (default 60).

Multi-host: host 0 owns the files; other hosts buffer in memory.
``parallel.distributed.initialize_distributed`` reports the real rank
via :func:`set_host`.

The run-level plane on top (ISSUE 4): ``obs.flops`` (analytic FLOPs →
MFU accounting), ``obs.anomaly``/``obs.flight`` (detectors + flight
-recorder ring + anomaly-triggered profiler windows; see
:func:`anomalies`), and ``obs.report`` (cross-host run reports, driven
by ``scripts/obsctl.py``).
"""

from __future__ import annotations

import atexit
import os
from typing import Optional

from huggingface_sagemaker_tensorflow_distributed_tpu.obs import core as _core
from huggingface_sagemaker_tensorflow_distributed_tpu.obs import (  # noqa: F401
    flops,
    programs,
)
from huggingface_sagemaker_tensorflow_distributed_tpu.obs.anomaly import (  # noqa: F401
    AnomalyDetector,
)
from huggingface_sagemaker_tensorflow_distributed_tpu.obs.core import (  # noqa: F401
    ENV_DIR,
    ENV_ENABLE,
    ENV_HEARTBEAT,
    EventLog,
    MetricsSink,
    NULL_SPAN,
    ObsState,
    Tracer,
)
from huggingface_sagemaker_tensorflow_distributed_tpu.obs.schema import (  # noqa: F401
    SCHEMA_VERSION,
    iter_events,
    validate_event,
    validate_events_file,
    validate_trace_file,
)
from huggingface_sagemaker_tensorflow_distributed_tpu.obs.flight import (  # noqa: F401
    FlightRecorder,
    ProfilerCapture,
)
from huggingface_sagemaker_tensorflow_distributed_tpu.obs.watchdog import (  # noqa: F401
    CompileTracker,
    Heartbeat,
    PauseMeter,
    install_compile_tracker,
    sample_device_memory,
    thread_stacks,
)

_state = ObsState()
_tracer = Tracer(_state)
_metrics = MetricsSink(_state)
_heartbeat: Optional[Heartbeat] = None
_pause_meter: Optional[PauseMeter] = None
_shutdown_at_exit = False
_detector: Optional[AnomalyDetector] = None


def state() -> ObsState:
    return _state


def enabled() -> bool:
    return _state.enabled


def has_sink() -> bool:
    """True when THIS process streams events to disk (host 0 of an
    instrumented run)."""
    return _state.events is not None


def configured() -> bool:
    """True when telemetry is enabled with an output dir. Unlike
    :func:`has_sink` this is identical on EVERY host of a launcher job
    (the env contract sets the dir everywhere; only host 0 gets the
    file), so it is the correct guard for collectives that feed
    telemetry — e.g. the per-epoch straggler gather."""
    return _state.enabled and _state.dir is not None


def configure(out_dir: Optional[str] = None,
              enabled: Optional[bool] = None) -> None:
    """Set the output directory and/or the enabled flag. While this
    process streams events to a directory the host-pause meter runs
    beside it (a 10 ms ticker thread, ``watchdog.PauseMeter``);
    :func:`shutdown` stops it and writes what it saw, at the
    interpreter's exit at the latest (with the spans still pending)."""
    global _pause_meter, _shutdown_at_exit
    _state.configure(out_dir=out_dir, enabled=enabled)
    if _pause_meter is None and has_sink():
        _pause_meter = PauseMeter(_state).start()
        if not _shutdown_at_exit:
            _shutdown_at_exit = True
            atexit.register(shutdown)


def set_host(index: int, count: int) -> None:
    _state.set_host(index, count)


def span(name: str, args: Optional[dict] = None):
    """Nestable wall-time span (context manager). Allocation-free when
    telemetry is disabled."""
    return _tracer.span(name, args)


def lifecycle_span(name: str, args: Optional[dict] = None):
    """A span of the process's life cycle (``serve/warmup``): kept in a
    small buffer even before a directory is configured and replayed
    into ``events.jsonl`` when one is. Never inside a step loop."""
    return _tracer.lifecycle_span(name, args)


def scalar(name: str, value, step: Optional[int] = None,
           args: Optional[dict] = None) -> None:
    _metrics.scalar(name, value, step, args)


def autotune(name: str, depth: int, reason: str,
             args: Optional[dict] = None) -> None:
    """One input-pipeline controller decision (``autotune`` event):
    ``name`` is the tuned knob, ``depth`` its new value, ``reason`` the
    trigger. No-op without a file sink, like :func:`scalar`."""
    if not _state.enabled or _state.events is None:
        return
    fields: dict = {"name": name, "depth": int(depth), "reason": str(reason)}
    if args:
        fields["args"] = args
    _state.events.emit("autotune", fields)


def serve(event: str, **fields) -> None:
    """One serving-engine lifecycle event (``serve`` event type):
    ``event`` names the transition (submit / admit / first_token /
    finish / preempt), extra keyword fields ride along (``request`` id,
    ``ttft_s``, ``tokens``, ...). No-op without a file sink."""
    if not _state.enabled or _state.events is None:
        return
    _state.events.emit("serve", {"event": str(event), **fields})


def anomalies() -> AnomalyDetector:
    """The process anomaly detector (created on first use; detectors
    read ``HSTD_ANOMALY`` / ``HSTD_ANOMALY_COOLDOWN_S`` /
    ``HSTD_STRAGGLER_ALERT``, the evidence side reads
    ``HSTD_FLIGHT_RING`` / ``HSTD_PROFILE_ON_ANOMALY``)."""
    global _detector
    if _detector is None:
        _detector = AnomalyDetector(_state, recorder=_state.ring)
    return _detector


def anomaly_counts() -> dict:
    """Per-kind anomaly counts so far ({} before any detector use)."""
    return dict(_detector.counts) if _detector is not None else {}


def anomaly_total() -> int:
    return _detector.total if _detector is not None else 0


def flight_recorder():
    """The process flight-recorder ring (None when HSTD_FLIGHT_RING=0)."""
    return _state.ring


def alert(name: str, message: str, args: Optional[dict] = None) -> None:
    """A budget/threshold warning (``alert`` event), mirrored to stderr
    by callers that need operator visibility."""
    if not _state.enabled or _state.events is None:
        return
    fields: dict = {"name": name, "message": message}
    if args:
        fields["args"] = args
    _state.events.emit("alert", fields)


def compile_budget_exceeded() -> bool:
    """True once the live compile tracker has crossed
    ``HSTD_COMPILE_BUDGET_S`` (latched; False with no budget or no
    tracker installed). Bucket-ladder batchers consult this to stop
    minting new widths."""
    from huggingface_sagemaker_tensorflow_distributed_tpu.obs.watchdog import (
        _INSTALLED,
    )

    return any(t.state is _state and t.budget_exceeded for t in _INSTALLED)


_budget_agreed = False


def set_compile_budget_agreed() -> None:
    """Latch the HOST-AGREED compile-budget crossing (ROADMAP
    "multi-host ladder capping"): the trainer calls this after the
    epoch-boundary collective (``parallel.distributed.
    agree_compile_budget_crossed``) reports that some host crossed
    ``HSTD_COMPILE_BUDGET_S``. Because every host latches from the SAME
    collective at the SAME epoch boundary, all hosts stop minting new
    bucket widths at the same step — which is what keeps multi-host
    bucket choices (derived from shared order + this flag) in
    agreement."""
    global _budget_agreed
    _budget_agreed = True


def compile_budget_agreed() -> bool:
    return _budget_agreed


def compile_budget_capped(process_count: int) -> bool:
    """Should a bucket ladder stop minting new widths? Single-host runs
    act on the local tracker the instant it crosses (mid-epoch is fine:
    there is nobody to disagree with); multi-host runs act only on the
    epoch-boundary agreed latch, so every host's ladder caps at the
    same step."""
    if process_count == 1:
        return compile_budget_exceeded()
    return _budget_agreed


def metrics() -> MetricsSink:
    return _metrics


def heartbeat_env_interval(default: float = 60.0) -> float:
    """``HSTD_HEARTBEAT_SECS`` as a float; malformed values fall back to
    ``default`` — telemetry configuration must never kill the workload
    it observes."""
    raw = os.environ.get(ENV_HEARTBEAT, "").strip()
    try:
        return float(raw) if raw else default
    except ValueError:
        return default


def heartbeat(interval: Optional[float] = None,
              stall_after: Optional[float] = None) -> Heartbeat:
    """The process heartbeat (created on first use; interval from
    ``HSTD_HEARTBEAT_SECS`` unless given)."""
    global _heartbeat
    if _heartbeat is None:
        if interval is None:
            interval = heartbeat_env_interval()
        _heartbeat = Heartbeat(_state, interval=interval,
                               stall_after=stall_after)
    return _heartbeat


def pulse() -> None:
    """Mark forward progress for the stall watchdog (hot path: two
    attribute stores; no-op until a heartbeat exists)."""
    hb = _heartbeat
    if hb is not None:
        hb.pulse()


def compile_tracker() -> Optional[CompileTracker]:
    return install_compile_tracker(_state)


def flush(program_maps: bool = True) -> None:
    """Write the pending spans to the event file and refresh trace.json
    from the span buffer; with a sink, also one ``program_map`` event
    for each registered program that has none yet (``obs.programs``:
    the first such call compiles, or finds in the compile cache, every
    registered program). ``program_maps=False`` is for a flush inside
    a region somebody times."""
    _state.flush_spans()
    _state.flush_trace()
    if program_maps:
        programs.flush(_state)


def shutdown() -> None:
    global _heartbeat, _detector, _pause_meter
    if _heartbeat is not None:
        _heartbeat.stop()
        _heartbeat = None
    if _pause_meter is not None:
        _pause_meter.stop()
        _pause_meter = None
    if _detector is not None:
        _detector.shutdown()     # close any open profiler window
        _detector = None
    programs.flush(_state)
    _state.shutdown()


def reset(out_dir: Optional[str] = None,
          enabled: Optional[bool] = None) -> ObsState:
    """Test helper: tear down and rebuild the process state (re-reading
    the environment), optionally overriding dir/enabled."""
    global _state, _tracer, _metrics, _heartbeat, _budget_agreed
    _budget_agreed = False
    shutdown()
    programs.reset()
    _state = ObsState()
    _tracer = Tracer(_state)
    _metrics = MetricsSink(_state)
    _heartbeat = None
    if out_dir is not None or enabled is not None:
        configure(out_dir=out_dir, enabled=enabled)
    return _state
