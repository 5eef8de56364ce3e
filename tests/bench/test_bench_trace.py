"""The trace reduction, on a small trace recorded on one TPU v5e chip by
``bench/record_trace_fixture.py``: three dispatches of a step holding the
flash kernels (forward, dq, dkv) and a matmul, under the harness's spans,
with a host pause between them."""
from __future__ import annotations

import os

import _bench_tiny  # noqa: F401  (puts bench/ on the path)
import pytest

from benchlib import spec, trace
from benchlib.peaks import peaks

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                       "fixture.xplane.pb")


@pytest.fixture(scope="module")
def tr():
    return trace.load(FIXTURE)


class Run:
    def __init__(self, tr, config, steps=3, tokens=3 * 256):
        self.trace, self.chips, self.config = tr, [0], config
        self.peaks, self.seq = peaks("TPU v5 lite"), 256
        self.tokens, self.steps = tokens, steps


def test_window_and_busy(tr):
    assert tr.window_s == pytest.approx(0.00945244, rel=1e-6)
    assert 0 < tr.busy_s(0) < tr.window_s
    # busy time and idle gaps tile the window
    gaps = sum(b - a for _, a, b in tr.idle_gaps(0)) * 1e-9
    assert tr.busy_s(0) + gaps == pytest.approx(tr.window_s, rel=1e-9)
    # the device clock is placed after the host's enqueues
    assert tr.shift[0] > 0


def test_idle_gaps_are_named_by_harness_spans(tr):
    names = {name for name, _, _ in tr.idle_gaps(0)}
    assert "bench/wait" in names
    assert "outside the harness's spans" in names      # the host's pause
    top = tr.top_gaps(0)
    assert top[0][0].startswith("outside the harness's spans")
    assert top[0][1] > 0.004                            # ~2 ms pause, twice


def test_top_ops(tr):
    top = tr.top_ops()
    assert 0 < len(top) <= 10
    secs = [s for _, s in top]
    assert secs == sorted(secs, reverse=True)
    assert any("flash_attention" in name for name, _ in top)


def test_flash_kernels_are_classified(tr):
    reader = spec.metric_reader("flash_roofline")
    kinds = [reader.kind_of(o) for o in tr.ops[0]]
    assert sorted(k for k in kinds if k) == ["dkv"] * 3 + ["dq"] * 3 + \
        ["fwd"] * 3


def test_per_layer_readers_on_the_fixture(tr):
    conf = {"hidden_size": 512, "num_attention_heads": 4,
            "num_key_value_heads": 2, "head_dim": 128,
            "intermediate_size": 1024, "num_hidden_layers": 1,
            "vocab_size": 1024, "reference": "dense"}
    run = Run(tr, conf)
    idle = spec.metric_reader("device.idle_share").read(run)
    assert idle == pytest.approx(100 * (1 - tr.busy_s(0) / tr.window_s))
    roof = spec.metric_reader("flash_roofline").read(run)
    assert 0 < roof <= 100
    assert spec.metric_reader("step.mfu").read(run) > 0
    # one chip runs no collective: there is nothing exposed to read
    assert tr.exposed_collective_s(0) is None


def test_collectives_exposed_only_where_nothing_else_runs():
    ops = {0: [trace.Op("ag", "all-gather-start", 0, 100, ""),
               trace.Op("f", "fusion", 50, 80, ""),
               trace.Op("ar", "all-reduce", 200, 260, ""),
               trace.Op("g", "fusion", 300, 400, "")]}
    t = trace.Trace(window=(0, 1000), ops=ops, shift={0: 0.0})
    # 100 + 60 ns of collectives, 30 of them under the fusion
    assert t.exposed_collective_s(0) == pytest.approx(130e-9)
    assert t.busy_s(0) == pytest.approx((100 + 60 + 100) * 1e-9)


@pytest.mark.parametrize("text,expect", [
    ("%fusion.12 = f32[4,2,3072]{2,1,0} fusion(f32[4,2,3072]{2,1,0} %p), "
     "kind=kLoop", ("fusion.12", "fusion", 1)),
    ("%jvp.1 = (bf16[4,256,128]{2,1,0}, f32[4,256,128]{2,1,0}) custom-call("
     "bf16[4,256,128]{2,1,0} %a, bf16[4,256,128]{2,1,0} %b, bf16[4,256,128]"
     "{2,1,0} %c), custom_call_target=\"tpu_custom_call\"",
     ("jvp.1", "custom-call", 3)),
    ("%all-gather-start.3 = (f32[1,512]{1,0}, f32[4,512]{1,0}) "
     "all-gather-start(f32[1,512]{1,0} %x), replica_groups={{0,1,2,3}}",
     ("all-gather-start.3", "all-gather-start", 1)),
])
def test_parse_instruction(text, expect):
    name, typ, opcode, operands = trace.parse_instruction(text)
    assert (name, opcode, len(operands)) == expect
    assert trace.shape_of(operands[0])[0] in (1, 4)
