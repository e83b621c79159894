"""32-virtual-device full-mesh correctness.

Spawns a child with a forced 32-device CPU backend (the conftest pins
this process to 8, so the wider mesh needs its own process) and asserts
the dp4 x fsdp2 x tp2 x sp2 training-step loss sequence matches a
1-device run exactly — all four parallelism axes at once, the shape the
8→32-chip scaling story runs on real hardware.
"""

import os
import subprocess
import sys

from huggingface_sagemaker_tensorflow_distributed_tpu.launch.launcher import cpu_sim_env

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_mesh32_full_axis_parity():
    child = os.path.join(_REPO, "tests", "_mesh32_child.py")
    env = cpu_sim_env(32)
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, child], env=env, cwd=_REPO,
                          stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                          text=True, timeout=900)
    assert proc.returncode == 0, proc.stdout[-4000:]
    assert "mesh32 ok" in proc.stdout
