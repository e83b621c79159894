"""Job launcher: hyperparameters → per-host training processes.

TPU-native replacement for the reference's SageMaker launcher
(``HuggingFace(entry_point=..., hyperparameters=..., distribution=...)``
+ ``estimator.fit()`` at reference ``launch.py:36-55``; SURVEY.md
component #1 / D11). The platform capabilities the reference buys from
AWS are provided in-repo:

- **hyperparam → argv serialization** (reference ``launch.py:51``; the
  platform turns the dict into ``--key value`` strings): ``to_argv``.
- **job naming** (``{base_job_name}-{timestamp}`` semantics of
  ``launch.py:52``): ``make_job_name``.
- **environment contract** (the platform sets ``SM_*`` env vars consumed
  at reference ``train.py:48-50``): the launcher sets
  ``TPU_OUTPUT_DATA_DIR`` / ``TPU_MODEL_DIR`` plus the multi-host
  coordination triplet ``TPU_COORDINATOR_ADDRESS`` /
  ``TPU_NUM_PROCESSES`` / ``TPU_PROCESS_ID`` consumed by
  ``parallel.distributed.initialize_distributed``.
- **process launch** (the platform's ``mpirun`` / per-node exec,
  reference ``launch.py:22``): two backends —
  ``LocalBackend`` spawns one process per simulated host on this machine
  (the "slice simulator": CPU devices + JAX coordinator on localhost, the
  multi-host test rig of SURVEY.md §4), and ``TPUVMBackend`` builds the
  ``gcloud compute tpus tpu-vm ssh --worker=all`` command for a real
  slice (zero-egress here, so it constructs and prints rather than
  executes by default).
- **artifact collection** (SageMaker tars ``SM_MODEL_DIR`` → S3 after
  exit, reference ``train.py:244`` call-stack note): job dirs keep
  per-host logs + the model/output dirs in one place.
"""

from __future__ import annotations

import datetime
import os
import shlex
import subprocess
import sys
import time
from dataclasses import dataclass, field
from typing import Optional

from huggingface_sagemaker_tensorflow_distributed_tpu.launch.slice import SliceConfig
from huggingface_sagemaker_tensorflow_distributed_tpu.parallel.distributed import (
    compilation_cache_dir,
)
from huggingface_sagemaker_tensorflow_distributed_tpu.utils.logging import get_logger

logger = get_logger(__name__)


def _free_port() -> int:
    import socket
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def cpu_sim_env(n_devices: int, base: Optional[dict] = None) -> dict:
    """Env overrides that force a virtual ``n_devices``-device CPU JAX
    backend in a child process — the slice-simulator recipe shared by
    ``LocalBackend`` and ``__graft_entry__.dryrun_multichip``."""
    env = dict(os.environ if base is None else base)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": (env.get("XLA_FLAGS", "") +
                      f" --xla_force_host_platform_device_count={n_devices}"),
    })
    return env


def to_argv(hyperparameters: dict) -> list[str]:
    """Serialize a hyperparameter dict to ``--key value`` CLI strings —
    the platform contract of reference ``launch.py:51`` (every value
    stringified; our typed config re-validates on parse)."""
    argv: list[str] = []
    for key, value in hyperparameters.items():
        argv.append(f"--{key}")
        if isinstance(value, bool):
            argv.append("true" if value else "false")
        else:
            argv.append(str(value))
    return argv


def make_job_name(base: str, when: Optional[float] = None) -> str:
    """``{base}-{YYYY-mm-dd-HH-MM-SS}`` (reference ``launch.py:52``
    derives the job name from the model name + timestamp)."""
    ts = datetime.datetime.fromtimestamp(
        time.time() if when is None else when)
    safe = base.replace("/", "-").replace("_", "-").strip("-")
    return f"{safe}-{ts.strftime('%Y-%m-%d-%H-%M-%S')}"


@dataclass
class TPUJob:
    """Estimator-style job description (reference ``launch.py:36-54``
    field parity: entry_point, source_dir, instance→slice, hyperparams,
    base_job_name)."""

    entry_point: str = "scripts/train.py"
    source_dir: str = "."
    slice_spec: str = "cpu-8"            # e.g. "v5e-32"; cpu-N = local simulator
    num_hosts: Optional[int] = None      # override (local simulator host count)
    hyperparameters: dict = field(default_factory=dict)
    base_job_name: str = "tpu-finetune"
    job_root: str = "/tmp/tpu_jobs"
    coordinator_port: Optional[int] = None   # None: pick a free port per job
    env: dict = field(default_factory=dict)

    def __post_init__(self):
        self.slice = SliceConfig.parse(self.slice_spec)

    def fit(self, wait: bool = True) -> "JobHandle":
        """Submit the job (``estimator.fit()`` parity, reference
        ``launch.py:55``)."""
        job_name = make_job_name(self.base_job_name)
        job_dir = os.path.join(self.job_root, job_name)
        os.makedirs(job_dir, exist_ok=True)
        backend = (LocalBackend() if self.slice.accelerator == "cpu"
                   else TPUVMBackend())
        handle = backend.launch(self, job_name, job_dir)
        if wait:
            handle.wait()
        return handle


class JobHandle:
    """A launched job: per-host processes (local) or a remote command."""

    def __init__(self, job_name: str, job_dir: str,
                 procs: Optional[list] = None,
                 remote_command: Optional[list[str]] = None):
        self.job_name = job_name
        self.job_dir = job_dir
        self.procs = procs or []
        self.remote_command = remote_command
        self.returncodes: Optional[list[int]] = None

    @property
    def model_dir(self) -> str:
        return os.path.join(self.job_dir, "model")

    @property
    def output_data_dir(self) -> str:
        return os.path.join(self.job_dir, "output")

    def wait(self, timeout: Optional[float] = None,
             grace_period: float = 10.0) -> list[int]:
        """Block until every host process exits; raise if any failed
        (MPI all-or-nothing semantics — the reference's platform kills
        the job when a rank dies, SURVEY.md §5.3).

        Polls ALL processes: as soon as one rank dies non-zero, the
        survivors (typically hung at the next collective waiting for the
        dead rank) get ``grace_period`` seconds, then are terminated —
        a sequential join on rank order would deadlock here.
        """
        if not self.procs:
            return []
        deadline = None if timeout is None else time.time() + timeout
        first_failure_at: Optional[float] = None
        while True:
            codes = [p.poll() for p in self.procs]
            if all(c is not None for c in codes):
                break
            now = time.time()
            failed = any(c not in (None, 0) for c in codes)
            if failed and first_failure_at is None:
                first_failure_at = now
            if first_failure_at is not None and now - first_failure_at > grace_period:
                self.terminate()
            if deadline is not None and now > deadline:
                self.terminate()
                raise subprocess.TimeoutExpired(
                    cmd=f"job {self.job_name}", timeout=timeout)
            time.sleep(0.2)
        self.returncodes = codes
        if any(codes):
            raise RuntimeError(
                f"job {self.job_name}: host(s) failed with codes {codes}; "
                f"logs under {self.job_dir}")
        return codes

    def terminate(self) -> None:
        for p in self.procs:
            if p.poll() is None:
                p.terminate()


class LocalBackend:
    """Slice simulator: K host processes on localhost, CPU devices each,
    JAX coordinator on 127.0.0.1 — the multi-host rig SURVEY.md §4 calls
    for (real rendezvous + collectives, no TPU, no cluster)."""

    def launch(self, job: TPUJob, job_name: str, job_dir: str) -> JobHandle:
        n_hosts = job.num_hosts or job.slice.num_hosts
        chips_per_host = max(1, job.slice.num_chips // max(1, n_hosts))
        # entry_point is resolved by the child relative to cwd=source_dir
        argv = [sys.executable, job.entry_point] + to_argv(job.hyperparameters)
        handle = JobHandle(job_name, job_dir)
        coord = f"127.0.0.1:{job.coordinator_port or _free_port()}"
        procs = []
        for host in range(n_hosts):
            env = dict(os.environ)
            env.update(job.env)
            env = cpu_sim_env(chips_per_host, base=env)
            env.update({
                "TPU_COORDINATOR_ADDRESS": coord,
                "TPU_NUM_PROCESSES": str(n_hosts),
                "TPU_PROCESS_ID": str(host),
                "TPU_OUTPUT_DATA_DIR": handle.output_data_dir,
                "TPU_MODEL_DIR": handle.model_dir,
                # telemetry (obs/): every host gets the rank-correct env;
                # only host 0 writes files (obs rank-0 discipline), into
                # the job dir next to the other artifacts
                "HSTD_TELEMETRY_DIR": env.get("HSTD_TELEMETRY_DIR")
                or os.path.join(handle.output_data_dir, "telemetry"),
                # one compile cache for every host and every job
                # (parallel/distributed.py::compilation_cache_dir):
                # repeat runs hit the disk cache instead of recompiling
                "JAX_COMPILATION_CACHE_DIR": env.get(
                    "JAX_COMPILATION_CACHE_DIR") or compilation_cache_dir(),
            })
            log_path = os.path.join(job_dir, f"host_{host}.log")
            with open(log_path, "w") as log:  # child inherits the fd
                procs.append(subprocess.Popen(
                    argv, env=env, stdout=log, stderr=subprocess.STDOUT,
                    cwd=job.source_dir))
        handle.procs = procs
        logger.info("local job %s: %d hosts × %d devices, logs in %s",
                    job_name, n_hosts, chips_per_host, job_dir)
        return handle


class TPUVMBackend:
    """Real-slice launch: builds the ``gcloud compute tpus tpu-vm ssh
    --worker=all`` command that starts one process per host (the
    TPU-native form of the reference's MPI distribution knob,
    ``launch.py:22``). Zero-egress environments construct the command;
    callers with network run it themselves or pass ``execute=True``."""

    def __init__(self, tpu_name: str = "$TPU_NAME", zone: str = "$ZONE",
                 project: Optional[str] = None, execute: bool = False):
        self.tpu_name = tpu_name
        self.zone = zone
        self.project = project
        self.execute = execute

    def launch(self, job: TPUJob, job_name: str, job_dir: str) -> JobHandle:
        entry = job.entry_point
        train_argv = ["python3", entry] + to_argv(job.hyperparameters)
        # an operator-set compile cache dir travels with the command;
        # otherwise each host uses <source_dir>/.jax_cache by the
        # entry point's own rule
        cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
        cache_env = (f"JAX_COMPILATION_CACHE_DIR={shlex.quote(cache_dir)} "
                     if cache_dir else "")
        remote = (
            f"cd {shlex.quote(job.source_dir)} && "
            f"TPU_OUTPUT_DATA_DIR={shlex.quote(os.path.join(job_dir, 'output'))} "
            f"TPU_MODEL_DIR={shlex.quote(os.path.join(job_dir, 'model'))} "
            + cache_env
            + " ".join(shlex.quote(a) for a in train_argv)
        )
        cmd = ["gcloud", "compute", "tpus", "tpu-vm", "ssh", self.tpu_name,
               f"--zone={self.zone}", "--worker=all",
               f"--command={remote}"]
        if self.project:
            cmd.insert(5, f"--project={self.project}")
        handle = JobHandle(job_name, job_dir, remote_command=cmd)
        if self.execute:
            with open(os.path.join(job_dir, "gcloud.log"), "w") as log:
                handle.procs = [subprocess.Popen(cmd, stdout=log,
                                                 stderr=subprocess.STDOUT)]
        else:
            # leave $VAR placeholders unquoted so the printed line still
            # expands from the operator's shell environment
            printable = " ".join(
                c if c.startswith("$") or "=$" in c else shlex.quote(c)
                for c in cmd)
            logger.info("job %s: run on the slice with:\n  %s", job_name,
                        printable)
        return handle
