"""Multi-host runtime initialization and topology queries.

TPU-native replacement for the reference's backend-select + init layer
(reference ``scripts/train.py:13-31``): where the reference picks
SMDDP vs Horovod at import time and calls ``hvd.init()`` for MPI/Gloo
rendezvous, we call ``jax.distributed.initialize`` against the JAX
coordinator service. Device pinning (``scripts/train.py:27-31``) has no
TPU equivalent — each host owns its local chips.

The reference's backend-swap capability (SMDDP vs Horovod vs none,
``launch.py:19-24``) maps to platform selection: a real TPU slice, a
single chip, or a virtual CPU mesh for tests — same trainer code.

Environment contract (set by our launcher, ``launch/launcher.py``):
``TPU_COORDINATOR_ADDRESS``, ``TPU_NUM_PROCESSES``, ``TPU_PROCESS_ID``.
On GCP TPU VMs all three are auto-detected by JAX and may be omitted.
"""

from __future__ import annotations

import os
import re

import jax

from huggingface_sagemaker_tensorflow_distributed_tpu.utils.logging import get_logger

logger = get_logger(__name__)

_INITIALIZED = False


ENV_CACHE_DIR = "JAX_COMPILATION_CACHE_DIR"
# <checkout>/.jax_cache (git-ignored): the path is part of the cache
# key's environment, so it is fixed, never per process or per job
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_CHECKOUT_CACHE_DIR = os.path.join(_CHECKOUT, ".jax_cache")


def compilation_cache_dir() -> str:
    """Where the persistent XLA compile cache lives: wherever
    ``JAX_COMPILATION_CACHE_DIR`` says, else one fixed directory inside
    the checkout. Every entry point, child process and ``chip_smoke.py``
    resolves it through this one rule."""
    return os.environ.get(ENV_CACHE_DIR) or _CHECKOUT_CACHE_DIR


def enable_compilation_cache() -> str:
    """Turn the persistent compile cache on at
    :func:`compilation_cache_dir`. With ``JAX_COMPILATION_CACHE_DIR``
    set, jax's own reading of it is the only setting and nothing is
    configured here. ``JAX_ENABLE_COMPILATION_CACHE=false`` (the test
    suite) keeps jax from reading or writing the directory at all. File
    names in source locations lose the checkout's path, so that a
    program's key is the same from any checkout (unless
    ``JAX_HLO_SOURCE_FILE_CANONICALIZATION_REGEX`` already says what to
    strip)."""
    if not os.environ.get(ENV_CACHE_DIR):
        jax.config.update("jax_compilation_cache_dir", _CHECKOUT_CACHE_DIR)
    if not jax.config.jax_hlo_source_file_canonicalization_regex:
        # a Mosaic kernel rides in its program as the bytes of its
        # module, source locations included, and the cache's key is over
        # those bytes (an ordinary operation's locations are left out of
        # it). With the checkout's path in the file names a copy of the
        # checkout elsewhere compiles every program that holds a kernel
        # anew (the decode steps: 23 s of set-up, chip run of PR 29;
        # PERF.md §6). Names relative to the checkout are the same
        # everywhere
        jax.config.update("jax_hlo_source_file_canonicalization_regex",
                          "^" + re.escape(_CHECKOUT + os.sep))
    return compilation_cache_dir()


class NoAcceleratorError(RuntimeError):
    """The default JAX backend is not a TPU and the CPU was not asked
    for by name."""


def require_accelerator() -> dict:
    """Initialize the backend and refuse a silent CPU fallback.

    jax falls back to the CPU with a warning when libtpu cannot
    initialize, and everything downstream then picks its CPU branch
    (XLA attention, interpret-mode Pallas, small presets) and exits 0.
    So unless ``jax_platforms`` (``JAX_PLATFORMS``, or the test suite's
    ``jax.config.update``) asks for ``cpu``, a default backend that is
    not a TPU is an error. Returns the device description every printed
    result carries."""
    devices = jax.devices()
    dev = devices[0]
    # the first platform listed is the default backend: "tpu,cpu" asks
    # for the TPU (and jax itself fails if a listed platform is missing)
    asked = (jax.config.jax_platforms or "").split(",")[0].strip().lower()
    if dev.platform != "tpu" and asked != "cpu":
        raise NoAcceleratorError(
            f"default JAX backend is {dev.platform!r} "
            f"({dev.device_kind}), not a TPU, and jax_platforms="
            f"{jax.config.jax_platforms!r} does not ask for the CPU — "
            "refusing to fall back; set JAX_PLATFORMS=cpu to run on the "
            "CPU on purpose")
    return {"platform": dev.platform, "device_kind": dev.device_kind,
            "device_count": len(devices), "jax_version": jax.__version__}


def device_memory_peaks() -> list[int] | None:
    """``peak_bytes_in_use`` of every local device, in device order;
    None where the backend keeps no memory statistics (the CPU)."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.local_devices()]
    return None if None in peaks else [int(p) for p in peaks]


def initialize_distributed() -> tuple[int, int]:
    """Initialize multi-host JAX if the env asks for it.

    Returns ``(process_index, process_count)`` — the parity of
    ``hvd.rank()`` / ``hvd.size()`` at host granularity (reference
    ``scripts/train.py:112,152``). Safe to call repeatedly and in
    single-process mode (no coordinator env → no-op).
    """
    global _INITIALIZED
    coord = os.environ.get("TPU_COORDINATOR_ADDRESS")
    nproc = os.environ.get("TPU_NUM_PROCESSES")
    pid = os.environ.get("TPU_PROCESS_ID")
    if not _INITIALIZED and coord and nproc and pid:
        jax.distributed.initialize(
            coordinator_address=coord,
            num_processes=int(nproc),
            process_id=int(pid),
        )
        _INITIALIZED = True
        logger.info(
            "distributed init: process %d/%d, coordinator %s",
            jax.process_index(), jax.process_count(), coord,
        )
    # telemetry learns the REAL rank (its import-time guess comes from
    # env vars, which auto-detected GCP TPU VM setups don't set): only
    # host 0 writes events.jsonl/trace.json on shared filesystems
    from huggingface_sagemaker_tensorflow_distributed_tpu import obs

    obs.set_host(jax.process_index(), jax.process_count())
    return jax.process_index(), jax.process_count()


def is_host0() -> bool:
    return jax.process_index() == 0


def host_step_stats(step_seconds: float) -> dict | None:
    """Per-host step-time aggregation for straggler visibility: every
    host contributes its mean step time; all return ``{n_hosts, min,
    max, mean, straggler_ratio}`` (rank 0 records it via the metrics
    sink). This is a COLLECTIVE on multi-host runs — every process must
    call it under the same condition. Returns the trivial single-host
    stats without touching any collective machinery when there is one
    process, and None when the value is not a finite number yet (first
    epoch shorter than one measured window)."""
    import math

    v = float(step_seconds)
    if not math.isfinite(v):
        return None
    if jax.process_count() == 1:
        return {"n_hosts": 1, "min": v, "max": v, "mean": v,
                "straggler_ratio": 1.0, "argmax": 0}
    import numpy as np
    from jax.experimental import multihost_utils

    vals = np.asarray(multihost_utils.process_allgather(
        np.asarray([v], np.float64))).reshape(-1)
    mean = float(vals.mean())
    return {"n_hosts": int(jax.process_count()),
            "min": float(vals.min()), "max": float(vals.max()),
            "mean": mean,
            "straggler_ratio": float(vals.max() / max(mean, 1e-12)),
            # the slow host's index (allgather order = process index):
            # what the straggler anomaly names
            "argmax": int(vals.argmax())}


def agree_compile_budget_crossed(local_crossed: bool) -> bool:
    """Epoch-boundary COLLECTIVE (multi-host): True iff ANY host's
    compile tracker has crossed ``HSTD_COMPILE_BUDGET_S``. The budget
    is crossed at a host-local instant (compiles race), so single-host
    ladder capping cannot be applied under multi-host — bucket choices
    must agree across hosts or ``global_arrays`` ships mismatched
    shapes into collectives. Calling this under an identical condition
    on every host (the trainer's epoch boundary, guarded by the
    env-driven budget setting) and latching the OR gives every host the
    same crossing step. Trivially local with one process."""
    if jax.process_count() == 1:
        return bool(local_crossed)
    import numpy as np
    from jax.experimental import multihost_utils

    vals = np.asarray(multihost_utils.process_allgather(
        np.asarray([1.0 if local_crossed else 0.0], np.float64)))
    return bool(vals.max() > 0.5)
