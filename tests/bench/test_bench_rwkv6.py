"""The Finch cell's pieces on the CPU at tiny widths: the program's loss and
gradient against the plain reference (``bench/reference/rwkv6.py``), one
tiny RWKV6 cell through the harness, the FLOP count of the configuration,
and the ``wkv.ms`` reader on a compiled RWKV6 epoch."""
from __future__ import annotations

import dataclasses
import json
import os
import time

import _bench_tiny
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import run as bench_run
from benchlib import flops_rwkv6, program, scopes, spec, stages
from benchlib import trace as trace_mod

ROOT = _bench_tiny.ROOT
# d_model 128, 2 heads of 64, d_ff 448, 2 layers, vocabulary 512
TINY = {"hidden_size": 128, "attention_hidden_size": 128, "head_size": 64,
        "head_size_divisor": 8, "intermediate_size": 448,
        "num_hidden_layers": 2, "vocab_size": 512, "layer_norm_epsilon": 1e-5,
        "assumed": {"time_mix_extra_dim": 32, "time_decay_extra_dim": 64},
        "reference": "rwkv6",
        "program": {"arch": "rwkv6-1.6b", "reduced": True, "d_ff": 448,
                    "act_dtype": "float32"}}


def tiny_cell():
    return dataclasses.replace(_bench_tiny.cell(), name="tiny-rwkv6",
                               config_name="tiny_rwkv6", config=TINY)


def _conf():
    with open(os.path.join(ROOT, "bench", "configs",
                           "rwkv6_finch_cut.json")) as fh:
        return json.load(fh)


# float32 replicas agree with the reference to rounding (loss 2e-6 and each
# leaf's gradient 2e-5 of its norm, on the CPU); bfloat16 replicas round
# each weight by up to 2^-9 and miss both by orders of magnitude
@pytest.mark.parametrize("param_dtype,agrees", [("float32", True),
                                                ("bfloat16", False)])
def test_program_loss_and_gradient_match_the_reference(param_dtype, agrees):
    cell = tiny_cell()
    ref = cell.reference
    bundle = program.experiment(cell).build_bundle()
    p0 = ref.init_params(jax.random.PRNGKey(3), TINY)
    assert jax.tree.structure(p0) == jax.tree.structure(
        jax.eval_shape(bundle.init, jax.random.PRNGKey(0)))
    toks = jax.random.randint(jax.random.PRNGKey(4), (2, 64), 0, 512)
    labels = jnp.roll(toks, -1, axis=1)
    mine = jax.tree.map(lambda a: a.astype(param_dtype).astype(jnp.float32),
                        p0)
    loss, grads = jax.value_and_grad(bundle.loss)(
        mine, {"tokens": toks, "labels": labels})
    with jax.default_matmul_precision("highest"):
        want, want_g = jax.value_and_grad(ref.loss)(p0, toks, labels, TINY)
    loss_gap = abs(float(loss) - float(want)) / float(want)
    leaf_gap = max(float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))
                   for a, b in zip(jax.tree.leaves(grads),
                                   jax.tree.leaves(want_g)))
    assert (loss_gap <= 1e-5 and leaf_gap <= 1e-4) == agrees, (loss_gap,
                                                              leaf_gap)


def test_tiny_cell_is_correct():
    res = bench_run.run_cell(tiny_cell(), 2 ** 40 + 7, 0.3, False,
                             jax.devices()[:1], time.time())
    assert res["correct"] is True and res["failed"] == 0
    for k, v in res["check"].items():
        assert v["value"] <= v["limit"], k


def _epoch_text(cell) -> str:
    """The compiled text of ``cell``'s epoch (compiled, not run)."""
    prog = program.build(cell, jax.devices()[:1])
    T, G = prog.pcfg.T, prog.pcfg.n_groups
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    state = jax.eval_shape(prog.make_state, key, key)
    rows = jax.ShapeDtypeStruct((T, G, 1, cell.traffic["seq"]), jnp.int32)
    zero = jax.ShapeDtypeStruct((), jnp.float32)
    return prog.engine._epoch.lower(state, {"tokens": rows, "labels": rows},
                                    zero, zero).compile().as_text()


@pytest.fixture(scope="module")
def rwkv_text():
    return _epoch_text(tiny_cell())


def test_finch_cut_counts():
    c = _conf()
    shapes = jax.eval_shape(lambda k: spec.load_module(os.path.join(
        ROOT, "bench", "reference", "rwkv6.py")).init_params(k, c),
        jax.random.PRNGKey(0))
    n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    D, F, V, R, Rd = 2048, 7168, 8192, 32, 64
    matrices = 5 * D * D + 2 * 5 * D * R + 2 * D * Rd + 2 * D * F + D * D
    vectors = 4 * D + 6 * D + D + D + 2 * D + 2 * D   # norms, mixes, w0, u
    assert n == 4 * (matrices + vectors) + 2 * V * D + 4 * D == 255_467_520
    assert flops_rwkv6.matmul_params(c) == 4 * matrices + V * D
    tokens = 4 * 1 * 2048                          # G x rows x sequence
    step = flops_rwkv6.model_flops_per_token(c) * tokens
    recurrence = 12 * 4 * 32 * 64 * 64 * tokens
    assert step == 6 * (4 * matrices + V * D) * tokens + recurrence
    assert step / 1e12 == pytest.approx(11.78, abs=0.01)


def test_wkv_ops_are_in_the_worker_gradient(rwkv_text):
    text = rwkv_text
    names = scopes.scoped_from_hlo(text, "wkv")
    assert names
    stage = stages.stages_from_hlo(text)
    assert {stage[n] for n in names} == {"worker_grad"}
    # the scan's loop, forward and backward, lies in the scope
    assert any(n.startswith("while") for n in names)


def test_in_scope_strips_transform_wrappers():
    assert scopes.in_scope(
        "jit(epoch)/worker_grad/transpose(jvp(wkv))/while/body/mul", "wkv")
    assert scopes.in_scope("vmap(checkpoint(wkv))/dot_general", "wkv")
    assert not scopes.in_scope("jit(epoch)/worker_grad/wkv", "wkv")
    assert not scopes.in_scope("worker_grad/wkv_shift/add", "wkv")


class _Run:
    def __init__(self, tr, text, steps):
        self.trace, self.chips, self.steps = tr, [0], steps
        self.hlo_text = text


def test_wkv_ms_sums_each_op_once(rwkv_text):
    text = rwkv_text
    names = sorted(scopes.scoped_from_hlo(text, "wkv"))
    other = next(n for n in stages.stages_from_hlo(text) if n not in names)
    ns = 1e6                                             # 1 ms

    def op(name, a, b):
        return trace_mod.Op(name, "fusion", a * ns, b * ns, name)

    ops = [op(names[0], 0, 2), op(names[1], 2, 5), op(other, 5, 9),
           op(names[0], 9, 12), op(names[1], 19, 24)]  # the last one half out
    tr = trace_mod.Trace(window=(0.0, 22 * ns), ops={0: ops})
    got = spec.metric_reader("wkv.ms").read(_Run(tr, text, steps=2))
    assert got == pytest.approx((2 + 3 + 3 + 3) / 2)


def test_wkv_ms_is_none_on_a_dense_program():
    text = _epoch_text(_bench_tiny.cell())
    assert not scopes.scoped_from_hlo(text, "wkv")
    tr = trace_mod.Trace(window=(0.0, 1e6), ops={0: []})
    assert spec.metric_reader("wkv.ms").read(_Run(tr, text, 1)) is None


def test_rwkv6_mfu_reads_only_an_rwkv6_configuration():
    tr = trace_mod.Trace(window=(0.0, 1e9), ops={0: []})
    run = bench_run.TracedRun(tr, [0], _conf(), {"flops_bf16": 197e12},
                              2048, 8192 * 5, 5)
    mfu = spec.metric_reader("rwkv6.step.mfu").read(run)
    assert mfu == pytest.approx(100 * 11.7768 * 5 / 197, rel=1e-4)
    run.config = _bench_tiny.CONFIGS["dense"]
    assert spec.metric_reader("rwkv6.step.mfu").read(run) is None
