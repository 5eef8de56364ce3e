"""The four-chip layout of a cell, one group per device, on four virtual
CPU devices: the run places its state over the mesh and its check passes."""
from __future__ import annotations

import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))


def test_four_device_run_is_correct():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "_bench_mesh_runner.py"),
         "dense"], capture_output=True, text=True, timeout=600, env=env,
        cwd=HERE)
    assert proc.returncode == 0, proc.stderr[-3000:]
    res = json.loads(proc.stdout.strip().splitlines()[-1])
    assert res["device"]["count"] == 4
    assert res["correct"] is True
