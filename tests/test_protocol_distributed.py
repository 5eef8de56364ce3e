"""Distributed ByzSGD protocol on 8 forced host devices (subprocess — the
device count must be set before jax initialises)."""
import os
import subprocess
import sys

import pytest


@pytest.mark.slow
def test_protocol_end_to_end():
    runner = os.path.join(os.path.dirname(__file__), "_protocol_runner.py")
    env = dict(os.environ)
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = os.path.abspath(src) + os.pathsep + env.get(
        "PYTHONPATH", "")
    out = subprocess.run([sys.executable, runner], env=env,
                         capture_output=True, text=True, timeout=1200)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "PROTOCOL_TESTS_PASS" in out.stdout, out.stdout


def _exp_2d_oracle(**env_extra):
    runner = os.path.join(os.path.dirname(__file__), "_exp_2d_runner.py")
    env = dict(os.environ, **env_extra)
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = os.path.abspath(src) + os.pathsep + env.get(
        "PYTHONPATH", "")
    out = subprocess.run([sys.executable, runner], env=env,
                         capture_output=True, text=True, timeout=1200)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "EXP_2D_ORACLE_PASS" in out.stdout, out.stdout


@pytest.mark.slow
def test_exp_2d_mesh_oracle():
    """lm/tfm_tiny through the protocol runner on the full (rep=4, fsdp=2)
    mesh vs the same spec pinned to one device: final params must agree —
    2D sharding is a layout decision, never a semantics one."""
    _exp_2d_oracle()


@pytest.mark.slow
def test_exp_2d_mesh_oracle_flash():
    """The same oracle with attention through the flash kernels (interpret
    mode): on a multi-device mesh they run per shard inside a shard_map
    laid out by the activation rules, which must not change what the
    protocol computes."""
    _exp_2d_oracle(REPRO_FLASH="1")
