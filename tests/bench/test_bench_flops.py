"""The benchmark's shape functions against hand counts, and its peaks."""
from __future__ import annotations

import json
import os

import _bench_tiny  # noqa: F401  (puts bench/ on the path)
import pytest

from benchlib import flops, peaks

ROOT = _bench_tiny.ROOT


def _conf(name):
    with open(os.path.join(ROOT, "bench", "configs", name + ".json")) as fh:
        return json.load(fh)


def test_phi4_step_is_13_tflop():
    c = _conf("phi4_mini_cut")
    D, F, V = 3072, 8192, 16384
    per_layer = D * D + 2 * D * 1024 + D * D + 3 * D * F
    n = 2 * per_layer + V * D
    assert n == 251_658_240                      # 251.7 M, all in products
    tokens = 4 * 1 * 2048                        # G x rows x sequence
    attention = 6 * 2 * 24 * 128 * 2048 * tokens
    step = flops.model_flops_per_token(c, 2048) * tokens
    assert step == pytest.approx(6 * n * tokens + attention, rel=1e-12)
    assert step / 1e12 == pytest.approx(13.0, abs=0.05)


@pytest.mark.parametrize("kind,matmuls", [("fwd", 2), ("dq", 3),
                                          ("dkv", 4)])
def test_flash_call_counts(kind, matmuls):
    heads, S, hd = 96, 2048, 128
    ops, nbytes = flops.flash_call(kind, heads, S, hd, kv_ratio=3)
    assert ops == matmuls * 2 * S * S * hd * heads / 2
    row = S * hd * 2
    kv = 2 * row / 3
    expect = {"fwd": 2 * row + kv + S * 4,
              "dq": 3 * row + kv + 2 * S * 4,
              "dkv": 2 * row + 2 * S * 4 + 2 * kv}[kind]
    assert nbytes == expect * heads
    # at these shapes every call is bound by its operations
    p = peaks.peaks("TPU v5 lite")
    assert ops / p["flops_bf16"] > nbytes / p["hbm_bytes_per_s"]


def test_unknown_device_kind_is_an_error():
    assert peaks.peaks("TPU v5 lite")["flops_bf16"] == 197e12
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks("TPU v4")
