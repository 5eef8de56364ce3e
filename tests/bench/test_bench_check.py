"""The check that decides ``correct`` must come out false when the timed
path is broken underneath: the control (the program's own bfloat16 replica
path where the configuration states float32) and each fault a training cell
can have. The whole run is driven on the CPU at tiny widths, past the
harness's look for a chip."""
from __future__ import annotations

import time

import _bench_tiny
import jax
import jax.numpy as jnp
import pytest

import run as bench_run
from benchlib import program


def _run(cell, prog=None):
    return bench_run.run_cell(cell, 7, 0.2, False, jax.devices()[:1],
                              time.time(), prog=prog)


def _assert_caught(res):
    assert res["correct"] is False
    assert res["failed"] > 0
    assert any(v["value"] > v["limit"] for v in res["check"].values())


def test_control_bfloat16_replicas_fail():
    cell = _bench_tiny.cell("dense")
    prog = program.build(cell, jax.devices()[:1],
                         {"param_dtype": "bfloat16"})
    _assert_caught(_run(cell, prog))


def test_state_returned_unchanged_fails(monkeypatch):
    from repro.core.epochs import EpochRunner
    monkeypatch.setattr(EpochRunner, "run_epoch",
                        lambda self, state, batches: (state, {}))
    _assert_caught(_run(_bench_tiny.cell("dense")))


def test_half_of_each_row_left_out_fails(monkeypatch):
    from repro.models.registry import ModelBundle
    loss = ModelBundle.loss

    def half(self, params, batch):
        n = batch["tokens"].shape[-1] // 2
        return loss(self, params, {k: v[..., :n] for k, v in batch.items()})

    monkeypatch.setattr(ModelBundle, "loss", half)
    _assert_caught(_run(_bench_tiny.cell("dense")))


def test_exchange_left_out_fails(monkeypatch):
    from repro.core import protocol

    def own(params, masks, cfg, mesh=None, rule=None):
        return params                       # every group keeps its replica

    def own_gradient(grads, weights, cfg, mesh=None):
        G = weights.shape[0]
        return jax.tree.map(lambda g: jnp.einsum(
            "sw,w...->s...", jnp.eye(G, dtype=g.dtype), g), grads)

    monkeypatch.setattr(protocol, "masked_pull", own)
    monkeypatch.setattr(protocol, "aggregate_gradients", own_gradient)
    _assert_caught(_run(_bench_tiny.cell("dense")))


@pytest.fixture(scope="module")
def planted():
    from control import fault_readings
    return fault_readings(_bench_tiny.cell("dense"), 7, jax.devices()[0])


@pytest.mark.parametrize("fault", ["no_exchange", "half_batch"])
def test_planted_reference_faults_read_above_the_limits(planted, fault):
    limits = _bench_tiny.LIMITS
    assert any(planted[fault][k] > limits[k] for k in limits)
