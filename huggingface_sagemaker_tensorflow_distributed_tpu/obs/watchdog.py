"""Watchdogs: liveness heartbeat (+ stall stack dump), host-pause
meter, XLA compile tracker, device-memory sampler.

Exactly the instrumentation that would have made the BENCH r05 rc=124
timeout diagnosable: a run that dies mid-compile leaves heartbeat lines
(so the last-known-alive time is on disk), compile events (so "it was
still compiling" is distinguishable from "it hung in the data loop"),
and — if the watched thread stops pulsing while the process lives — a
full stack dump naming the blocked thread.

jax is imported inside functions only: the heartbeat and stall machinery
must work in processes that never initialize a backend (the bench
supervisor), and ``obs`` must stay importable without jax.
"""

from __future__ import annotations

import heapq
import os
import sys
import threading
import time
import traceback
from typing import Optional

from huggingface_sagemaker_tensorflow_distributed_tpu.obs.core import ObsState

# plain stdlib logging, NOT utils.logging: that package's __init__ pulls
# jax, and obs must stay importable (and the schema validator runnable)
# on jax-less boxes. Runs that configured utils.logging still format
# these records — it configures the root logger.
import logging

logger = logging.getLogger(__name__)


def thread_stacks() -> list[dict]:
    """All live threads' stacks as schema ``stall.threads`` entries."""
    frames = sys._current_frames()
    out = []
    for th in threading.enumerate():
        frame = frames.get(th.ident)
        stack = traceback.format_stack(frame) if frame is not None else []
        out.append({"name": th.name, "ident": th.ident & 0x7FFFFFFF,
                    "daemon": th.daemon,
                    "stack": [ln.rstrip("\n") for ln in stack]})
    return out


class Heartbeat:
    """Daemon thread emitting one liveness line every ``interval`` secs.

    The thread being watched (whoever calls :meth:`pulse` — the train
    loop, the bench body) registers progress; if no pulse lands for
    ``stall_after`` seconds while the process is otherwise alive, the
    heartbeat emits ONE ``stall`` event with every thread's stack and
    the watched thread's name, then re-arms when pulses resume.

    ``pulse()`` is allocation-free: two attribute stores.
    """

    def __init__(self, state: ObsState, interval: float = 60.0,
                 stall_after: Optional[float] = None,
                 sample_memory: bool = True):
        self._state = state
        self.interval = max(float(interval), 0.05)
        self.stall_after = (stall_after if stall_after is not None
                            else 3.0 * self.interval)
        self.sample_memory = sample_memory
        self._t0 = time.monotonic()
        self._progress = 0
        self._last_pulse = self._t0
        self._watched = "main"
        self._watched_ident = threading.main_thread().ident
        self._watching = False
        self._dumped = False
        self._last_trace_n = -1
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self.stall_count = 0

    # -- watched-thread side (hot path) -------------------------------------

    def pulse(self) -> None:
        self._progress += 1
        self._last_pulse = time.monotonic()

    def watch_current_thread(self) -> None:
        th = threading.current_thread()
        self._watched = th.name
        self._watched_ident = th.ident
        self._watching = True
        self._last_pulse = time.monotonic()

    def unwatch(self) -> None:
        """Disable stall detection (liveness beats continue) — call when
        the watched loop finishes and legitimate idleness begins."""
        self._watching = False

    # -- thread management --------------------------------------------------

    def start(self) -> "Heartbeat":
        if self._thread is None or not self._thread.is_alive():
            self._stop.clear()
            self._last_pulse = time.monotonic()
            self._thread = threading.Thread(target=self._run,
                                            name="hstd-heartbeat",
                                            daemon=True)
            self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=2 * self.interval)
            self._thread = None

    # -- heartbeat thread ---------------------------------------------------

    def _beat_once(self) -> None:
        now = time.monotonic()
        age = now - self._last_pulse
        if self._state.events is not None:
            self._state.events.emit("heartbeat", {
                "uptime": round(now - self._t0, 3),
                "progress": self._progress,
                "progress_age": round(age, 3)})
        if self.sample_memory:
            sample_device_memory(self._state)
        # spans wait in memory for a batch: a beat is one, so a later
        # SIGKILL loses at most an interval of them
        self._state.flush_spans()
        # keep trace.json current: a later SIGKILL still leaves a valid,
        # recent Chrome trace on disk (atomic replace). The rewrite is
        # O(buffered spans), so skip it unless enough NEW spans landed
        # to matter — end-of-fit/shutdown flushes cover the final state.
        n_spans = len(self._state.spans)
        if n_spans != self._last_trace_n and (
                n_spans - self._last_trace_n >= 256 or n_spans < 4096):
            try:
                self._state.flush_trace()
                self._last_trace_n = n_spans
            except OSError:
                pass
        if self._watching and age > self.stall_after:
            if not self._dumped:
                self._dumped = True
                self.stall_count += 1
                self._dump_stall(age)
        else:
            self._dumped = False

    def _dump_stall(self, age: float) -> None:
        threads = thread_stacks()
        watched = self._watched
        for th in threads:
            if th["ident"] == (self._watched_ident or 0) & 0x7FFFFFFF:
                th["watched"] = True
                watched = th["name"]
        if self._state.events is not None:
            self._state.events.emit("stall", {
                "progress_age": round(age, 3), "stalled": watched,
                "progress": self._progress, "threads": threads})
        lines = [f"[hstd-heartbeat] STALL: thread {watched!r} made no "
                 f"progress for {age:.1f}s (progress={self._progress}); "
                 "all thread stacks follow"]
        for th in threads:
            mark = " <-- watched (blocked)" if th.get("watched") else ""
            lines.append(f"--- thread {th['name']!r}"
                         f" (daemon={th['daemon']}){mark}")
            lines.extend(th["stack"])
        dump = "\n".join(lines)
        print(dump, file=sys.stderr, flush=True)
        logger.error("heartbeat stall: %r blocked for %.1fs",
                     watched, age)
        # anomaly plane (obs/anomaly.py): a stall is an incident — give
        # it a flight dump + index entry next to the stack dump. Only an
        # ALREADY-CREATED detector is notified (the heartbeat thread
        # must not instantiate policy objects behind the run's back).
        try:
            obs_pkg = sys.modules.get(
                "huggingface_sagemaker_tensorflow_distributed_tpu.obs")
            det = getattr(obs_pkg, "_detector", None)
            if det is not None and det._state is self._state:
                det.observe_stall(age, watched)
        except Exception:  # noqa: BLE001 — liveness must not kill runs
            logger.exception("stall anomaly notification failed")

    def _run(self) -> None:
        while not self._stop.wait(self.interval):
            try:
                self._beat_once()
            except Exception:  # noqa: BLE001 — liveness must not kill runs
                logger.exception("heartbeat emission failed")


class PauseMeter:
    """Daemon thread that sleeps ``interval`` and records by how much
    each wake-up came LATE: while this thread cannot run, nothing of
    the process's Python can (the machine paused the guest, a garbage
    collection or another call held the interpreter for its whole
    length). It tells "the host stood still" from "the device did not
    answer", which a blocked fetch alone cannot.

    :meth:`stop` emits one ``metric`` ``host/pause_max_s`` and one
    ``host_pause`` event (``mono``, ``dur``: on the spans' clock, from
    when the wake-up was due) for each wake-up more than ``threshold``
    late, the ``keep`` longest at most. ``clock`` and ``sleep`` are
    injectable so that a test can make a wake-up late by hand.
    """

    def __init__(self, state: ObsState, interval: float = 0.010,
                 threshold: float = 0.020, keep: int = 256,
                 clock=time.perf_counter, sleep=time.sleep):
        self._state = state
        self.interval, self.threshold, self.keep = interval, threshold, keep
        self._clock, self._sleep = clock, sleep
        self.max_late_s = 0.0
        self._pauses: list = []        # min-heap of (late, due)
        self._stop = False
        self._thread: Optional[threading.Thread] = None

    def cycle(self) -> None:
        """One sleep and its lateness."""
        due = self._clock() + self.interval
        self._sleep(self.interval)
        late = self._clock() - due
        if late > self.max_late_s:
            self.max_late_s = late
        if late > self.threshold:
            push = (heapq.heappush if len(self._pauses) < self.keep
                    else heapq.heappushpop)
            push(self._pauses, (late, due))

    def _run(self) -> None:
        while not self._stop:
            self.cycle()

    def start(self) -> "PauseMeter":
        if self._thread is None:
            self._stop = False
            self._thread = threading.Thread(
                target=self._run, name="hstd-pause-meter", daemon=True)
            self._thread.start()
        return self

    def stop(self) -> None:
        """Stop the thread and write what it saw."""
        self._stop = True
        if self._thread is not None:
            self._thread.join(timeout=1.0 + 10 * self.interval)
            self._thread = None
        events = self._state.events
        if events is None:
            return
        events.emit("metric", {"name": "host/pause_max_s",
                               "value": round(self.max_late_s, 6)})
        mono0 = self._state.mono0
        events.emit_many("host_pause", [
            {"mono": round(due - mono0, 6), "dur": round(late, 6)}
            for late, due in sorted(self._pauses, key=lambda p: p[1])])


ENV_COMPILE_BUDGET = "HSTD_COMPILE_BUDGET_S"


def compile_budget_env() -> Optional[float]:
    """``HSTD_COMPILE_BUDGET_S`` as a float (None = no budget; malformed
    values disable rather than kill the run — telemetry configuration
    must never take the workload down)."""
    raw = os.environ.get(ENV_COMPILE_BUDGET, "").strip()
    try:
        return float(raw) if raw else None
    except ValueError:
        return None


class CompileTracker:
    """Counts every XLA compilation via ``jax.monitoring`` listeners.

    ``count`` is the number of backend compile requests
    (``backend_compile_duration``: one per executable, persistent-cache
    disk hits included, with near-zero durations); ``cum_secs`` adds the
    lowering stage, and one ``compile`` event is emitted per observed
    stage — the compile-vs-data-vs-step attribution the throughput
    accounting needs. ``jaxpr_trace_duration`` is NOT observed: jax
    records it for every nested trace (thousands per model, each outer
    one containing the inner ones) and on every miss of jit's C++
    dispatch cache (e.g. the first call that passes a device array
    where warm-up passed numpy) even when the traced program and its
    executable are already cached, so it is neither a compilation nor
    additive. Listener registration is process-global in jax and
    cannot be unregistered, so ``install`` wires one module-level hook
    that follows the live ObsState.

    With a compile budget (``HSTD_COMPILE_BUDGET_S``, ROADMAP
    "Compile-time budget"), the first crossing of cumulative compile
    seconds emits ONE ``alert`` event plus a stderr line, and
    ``budget_exceeded`` latches — bucket-ladder batchers consult it
    (via ``obs.compile_budget_exceeded``) to stop minting new widths.
    """

    LOWERING = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self, state: ObsState, budget_s: Optional[float] = None):
        self.state = state
        self.count = 0
        self.cum_secs = 0.0
        self.budget_s = compile_budget_env() if budget_s is None else budget_s
        self.budget_exceeded = False
        self._lock = threading.Lock()

    def observe(self, event: str, secs: float, **_kwargs) -> None:
        if event not in (self.LOWERING, self.BACKEND_COMPILE):
            return
        crossed = False
        with self._lock:
            if event == self.BACKEND_COMPILE:
                self.count += 1
            self.cum_secs += secs
            count, cum = self.count, self.cum_secs
            if (self.budget_s is not None and cum > self.budget_s
                    and not self.budget_exceeded):
                self.budget_exceeded = True
                crossed = True
        if self.state.events is not None:
            self.state.events.emit("compile", {
                "event": event, "dur": round(secs, 6), "count": count,
                "cum": round(cum, 3)})
        if crossed:
            msg = (f"cumulative XLA compile time {cum:.1f}s exceeds "
                   f"{ENV_COMPILE_BUDGET}={self.budget_s:g}s after "
                   f"{count} compilations — bucket ladders will stop "
                   "minting new widths; consider fewer bucket rungs")
            if self.state.events is not None:
                self.state.events.emit("alert", {
                    "name": "compile_budget", "message": msg,
                    "cum": round(cum, 3), "budget_s": self.budget_s,
                    "count": count})
            print(f"[hstd-obs] COMPILE BUDGET: {msg}", file=sys.stderr,
                  flush=True)
            logger.warning("compile budget exceeded: %s", msg)


_INSTALLED: list[CompileTracker] = []


def install_compile_tracker(state: ObsState) -> Optional[CompileTracker]:
    """Idempotent per ObsState; returns the tracker (None if telemetry
    is disabled)."""
    if not state.enabled:
        return None
    for tracker in _INSTALLED:
        if tracker.state is state:
            return tracker
    from jax import monitoring

    tracker = CompileTracker(state)
    monitoring.register_event_duration_secs_listener(tracker.observe)
    _INSTALLED.append(tracker)
    return tracker


def sample_device_memory(state: ObsState) -> int:
    """Emit one ``memory`` event per local device reporting memory_stats
    (TPU/GPU). Graceful no-op — returns 0 — on CPU backends, before jax
    is imported anywhere, or if jax is not even importable."""
    if not state.enabled or state.events is None:
        return 0
    if "jax" not in sys.modules:
        return 0  # never force a backend init from the telemetry layer
    jax = sys.modules["jax"]
    try:
        devices = jax.local_devices()
    except Exception:  # noqa: BLE001 — backend not initialized / gone
        return 0
    emitted = 0
    for d in devices:
        try:
            stats = d.memory_stats()
        except Exception:  # noqa: BLE001 — CPU backend raises on some jaxlibs
            stats = None
        if not stats:
            continue
        state.events.emit("memory", {
            "device": f"{d.platform}:{d.id}",
            "stats": {k: int(v) for k, v in stats.items()
                      if isinstance(v, (int, float))}})
        emitted += 1
    return emitted
