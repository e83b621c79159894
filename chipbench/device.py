"""What the benchmark takes from the device and the runtime itself:
device facts, memory peaks, compiles counted by its own listener, and
the profiler session of a traced run."""

from __future__ import annotations

import os
import tempfile
import threading
import time
from typing import Any, NamedTuple, Optional

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class NoChipError(RuntimeError):
    """No accelerator, or fewer chips than the cell asks for."""


def asked_for_cpu() -> bool:
    """True when the FIRST platform of ``jax_platforms`` is ``cpu``: the
    rehearsal. (``tpu,cpu``, as the chip's machine sets it, asks for
    the TPU.)"""
    import jax

    asked = (jax.config.jax_platforms or "").split(",")[0].strip().lower()
    return asked == "cpu"


def devices_for(chips: int) -> list:
    """The first ``chips`` devices; fails where the default backend is
    not a TPU (unless the CPU was asked for by name) or holds fewer."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" and not asked_for_cpu():
        raise NoChipError(
            f"default JAX backend is {devs[0].platform!r}, not a TPU; "
            "JAX_PLATFORMS=cpu runs the rehearsal on purpose")
    if len(devs) < chips:
        raise NoChipError(f"the cell needs {chips} chip(s), JAX has "
                          f"{len(devs)}")
    return list(devs[:chips])


def device_facts(devs: list) -> dict:
    import jax

    stats = [d.memory_stats() or {} for d in devs]
    peaks = [s.get("peak_bytes_in_use") for s in stats]
    limits = [s.get("bytes_limit") for s in stats]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs),
            "memory_peak_bytes": (max(peaks) if None not in peaks else 0),
            "memory_limit_bytes": (min(limits) if None not in limits else 0),
            "jax_version": jax.__version__}


class CompileCounter:
    """Backend compiles, by the benchmark's own ``jax.monitoring``
    listener on the exact event name (jax 0.9 calls listeners with
    keyword arguments, PR 21). ``in_window`` counts those observed
    after :meth:`open_window`."""

    def __init__(self):
        self._lock = threading.Lock()
        self.total = 0
        self.total_s = 0.0
        self._opened_at: Optional[int] = None
        self._closed_at: Optional[int] = None

    def install(self) -> "CompileCounter":
        from jax import monitoring

        monitoring.register_event_duration_secs_listener(self._observe)
        return self

    def _observe(self, event: str, secs: float, **_kw) -> None:
        if event == BACKEND_COMPILE:
            with self._lock:
                self.total += 1
                self.total_s += secs

    def open_window(self) -> None:
        self._opened_at = self.total

    def close_window(self) -> None:
        self._closed_at = self.total

    @property
    def in_window(self) -> int:
        if self._opened_at is None:
            return 0
        end = self.total if self._closed_at is None else self._closed_at
        return end - self._opened_at


class TraceSession:
    """The profiler over a few steady seconds of a traced run. The
    caller polls :meth:`tick` from its loop with the seconds since the
    window opened. With ``length_s`` the session stops itself (a loop
    whose host runs far ahead of the device loses nothing by the
    stall); without, the caller calls :meth:`stop` once its window has
    closed, so that stopping the profiler stalls nothing that counts.
    How long the trace is, the trace itself says
    (``reduce.window_seconds``): the host's clock around ``start_trace``
    and ``stop_trace`` is not the span the device events cover."""

    def __init__(self, start_s: float, length_s: Optional[float] = None,
                 out_dir: Optional[str] = None):
        self.start_s, self.length_s = float(start_s), length_s
        self.dir = out_dir or tempfile.mkdtemp(prefix="chipbench_trace_")
        self.state = "waiting"      # -> "tracing" -> "stopped"

    def tick(self, now_s: float) -> None:
        import jax

        if self.state == "waiting" and now_s >= self.start_s:
            jax.profiler.start_trace(self.dir)
            self.state = "tracing"
        elif self.state == "tracing" and self.length_s is not None \
                and now_s >= self.start_s + self.length_s:
            self.stop()

    def stop(self) -> None:
        import jax

        if self.state == "tracing":
            jax.profiler.stop_trace()
        self.state = "stopped"


class Observed(NamedTuple):
    """Everything a per-layer reader may read."""
    cell: Any               # spec.Cell
    device_kind: str
    chips: int
    window_s: float         # the measured window
    values: dict            # end-to-end values of this run, by name
    counters: dict          # the driver's own counts, sizes and samples
    events: list            # the program's telemetry events (traced run)
    trace: Any              # reduce.Trace or None
    trace_window_s: float   # reduce.window_seconds(trace)
    memory_peak_bytes: int
    memory_limit_bytes: int
    compiles_in_window: int


class SetupMarks:
    """Seconds since the process started at which each stage of set-up
    was done; printed with the run so that set-up can be attributed."""

    def __init__(self, t_start: float):
        self._t_start = t_start
        self._marks: list = []
        self("imports")

    def __call__(self, name: str) -> None:
        self._marks.append((name, time.perf_counter() - self._t_start))

    def as_dict(self) -> dict:
        return {k: round(v, 2) for k, v in self._marks}


def annotate(name: str):
    """A host span in the profiler's own trace (``chipbench/<name>``)."""
    import jax

    return jax.profiler.TraceAnnotation("chipbench/" + name)


def read_events(out_dir: str) -> list:
    """The program's telemetry events of this run (``events.jsonl``);
    the temporary directory is removed once read."""
    import json
    import shutil

    path = os.path.join(out_dir, "events.jsonl")
    out = []
    if os.path.exists(path):
        with open(path, encoding="utf-8") as f:
            for line in f:
                try:
                    out.append(json.loads(line))
                except ValueError:
                    continue
    shutil.rmtree(out_dir, ignore_errors=True)
    return out
