"""The stage readers (``benchlib.stages``, ``bench/metrics/<stage>.ms.py``,
``stage.unattributed_share``), on a small trace recorded on one TPU v5e chip
by ``bench/record_stage_fixture.py``: two epochs of a tiny ``ProtocolEngine``
(``tiny_engine``: two stacked dense layers, G=4, T=2) through the harness's
loop, under its spans and the program's ``repro/run_epoch``; beside it, the
stages that the compiled epoch's text gives the instructions the trace ran
(the readers' route)."""
from __future__ import annotations

import json
import os

import _bench_tiny  # noqa: F401  (puts bench/ on the path)
import jax
import pytest

from benchlib import spec, stages, trace
from repro.core import protocol

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
TRACE = os.path.join(DATA, "stages.xplane.pb")
STEPS = 4                                        # two epochs of T=2
METRICS = [f"{s}.ms" for s in protocol.STAGES] + ["stage.unattributed_share"]


class Run:
    def __init__(self, tr, stage_by):
        self.trace, self.chips, self.steps = tr, [0], STEPS
        self.stage_by = stage_by


@pytest.fixture(scope="module")
def loaded():
    return stages.load(TRACE)


@pytest.fixture(scope="module")
def by_name():
    with open(os.path.join(DATA, "stages.by_name.json")) as fh:
        return json.load(fh)


def test_the_benchmark_lists_a_reader_for_each_stage():
    with open(os.path.join(_bench_tiny.ROOT, "BENCHMARK.json")) as fh:
        listed = {m["name"]: m for m in json.load(fh)["per_layer"]}
    for name in METRICS:
        assert listed[name]["layer"] == "ByzSGD step"
        assert listed[name]["moves"] == "tokens_per_s"


@pytest.mark.parametrize("name", METRICS)
def test_every_stage_metric_reads(loaded, by_name, name):
    value = spec.metric_reader(name).read(Run(loaded[0], by_name))
    assert value is not None and value > 0
    if name == "stage.unattributed_share":
        assert value < 100


def test_stages_and_unattributed_tile_the_busy_time(loaded, by_name):
    tr = loaded[0]
    st = stages.stage_times(tr, [0], STEPS, by_name)
    assert set(st.stage_s) == set(protocol.STAGES)
    total = sum(st.stage_s.values()) + st.unattributed_s
    assert total == pytest.approx(st.busy_s, rel=0.01)
    assert st.busy_s == pytest.approx(tr.busy_s(0) / STEPS, rel=1e-9)


def test_the_compiled_text_agrees_with_the_trace_s_own_op_names(loaded,
                                                                by_name):
    tr, by_trace = loaded
    named = [op.name for op in tr.ops[0] if by_trace.get(op.name)]
    assert named
    for name in named:
        assert by_name.get(name) == by_trace[name], name
    # the text also places the ops the compiler made without metadata
    a = stages.stage_times(tr, [0], STEPS, by_trace)
    b = stages.stage_times(tr, [0], STEPS, by_name)
    assert b.unattributed_s <= a.unattributed_s


def test_the_wire_reader_finds_tf_op_in_the_older_fixture():
    path = os.path.join(DATA, "fixture.xplane.pb")
    names = stages.op_names_from_xplane(path)
    assert any(v.startswith("jit(step)/transpose(jvp(jit(flash_attention)))")
               for v in names.values())
    assert any(op.text in names for op in trace.load(path).ops[0])


def test_program_spans_name_gaps_and_leave_the_window(loaded):
    tr = loaded[0]
    base = trace.load(TRACE)
    assert tr.window == base.window
    runs = [sp for sp in tr.spans if sp[0] == "repro/run_epoch"]
    dispatch = [sp for sp in tr.spans if sp[0] == "bench/dispatch_epoch"]
    assert len(runs) == len(dispatch) == 2
    for _, a, b in runs:                      # each inside a dispatch span
        assert any(da <= a and b <= db for _, da, db in dispatch)
    assert "repro/run_epoch" in {name for name, _, _ in tr.idle_gaps(0)}
    # the harness's own reading names the same gaps by its spans alone
    assert "repro/run_epoch" not in {name for name, _, _ in
                                     base.idle_gaps(0)}


def test_readers_read_none_without_named_stages(loaded, by_name,
                                                monkeypatch):
    monkeypatch.setattr(stages, "STAGES", ())
    for name in METRICS:
        assert spec.metric_reader(name).read(Run(loaded[0], by_name)) is None


def test_a_stage_that_ran_no_op_reads_none(loaded, by_name):
    kept = {k: v for k, v in by_name.items() if v != "gather"}
    run = Run(loaded[0], kept)
    assert spec.metric_reader("gather.ms").read(run) is None
    assert spec.metric_reader("pull.ms").read(run) > 0


def test_stage_time_is_clipped_to_the_window_and_averaged_over_chips():
    def op(name, a, b):
        return trace.Op(name, "fusion", a, b, f"%{name} = f32[] fusion()")

    # window 0..10 ms on the host; chip 1's clock runs 1 ms behind
    tr = trace.Trace(window=(0.0, 10e6), shift={0: 0.0, 1: 1e6}, ops={
        0: [op("p", -2e6, 2e6), op("g", 2e6, 5e6), op("x", 5e6, 6e6)],
        1: [op("p", -1e6, 3e6), op("g", 3e6, 4e6)]})
    by = {"p": "pull", "g": "gather", "x": None}
    st = stages.stage_times(tr, [0, 1], 2, by)
    # pull: 2 ms on chip 0, 4 ms on chip 1 inside its window (-1..9 ms)
    assert st.stage_s["pull"] == pytest.approx((2e-3 + 4e-3) / 2 / 2)
    assert st.stage_s["gather"] == pytest.approx((3e-3 + 1e-3) / 2 / 2)
    assert st.unattributed_s == pytest.approx(1e-3 / 2 / 2)
    assert "update" not in st.stage_s
    run = Run(tr, by)
    run.chips, run.steps = [0, 1], 2
    assert spec.metric_reader("pull.ms").read(run) == pytest.approx(1.5)
    assert spec.metric_reader("update.ms").read(run) is None
    assert spec.metric_reader("stage.unattributed_share").read(
        run) == pytest.approx(100 * 0.25 / ((6 + 5) / 4))


HLO = """\
HloModule jit_epoch

%fused (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  ROOT %n = f32[4]{0} negate(f32[4]{0} %p), metadata={op_name="x/update/neg"}
}

%branch (b: f32[4]) -> f32[4] {
  %b = f32[4]{0} parameter(0)
  %z = f32[4]{0} broadcast(f32[] %c), metadata={op_name="jit(epoch)/while"}
  %m = f32[4]{0} sort(f32[4]{0} %b), metadata={op_name="x/cond/gather/sort"}
  ROOT %copy.1 = f32[4]{0} copy(f32[4]{0} %m)
}

%body (s: f32[4]) -> f32[4] {
  %s = f32[4]{0} parameter(0)
  %a = f32[4]{0} add(f32[4]{0} %s, f32[4]{0} %s), metadata={op_name="x/pull/add"}
  %dus = f32[4]{0} fusion(f32[4]{0} %a), kind=kLoop, calls=%fused
  %g = f32[4]{0} multiply(f32[4]{0} %dus, f32[4]{0} %dus), metadata={op_name="x/worker_grad/vmap(mul)/mul"}
  %copy.2 = s32[] copy(s32[] %k)
  ROOT %c = f32[4]{0} conditional(pred[] %q, f32[4]{0} %g), branch_computations={%branch}, metadata={op_name="x/cond"}
}

ENTRY %main (x: f32[4]) -> f32[4] {
  %x = f32[4]{0} parameter(0)
  ROOT %w = f32[4]{0} while(f32[4]{0} %x), condition=%body, body=%body, metadata={op_name="jit(epoch)/while"}
}
"""


def test_an_op_made_without_metadata_takes_the_stage_around_it():
    by = stages.stages_from_hlo(HLO)
    assert by["a"] == "pull" and by["g"] == "worker_grad"
    assert by["dus"] == "pull"            # from its operand
    assert by["n"] == "update"            # its own op_name, inside a fusion
    assert by["copy.1"] == "gather"       # the branch's one stage
    assert by["z"] is None                # its own op_name names no stage
    assert by["copy.2"] is None           # a body of several stages, in a
    assert by["w"] is None and by["c"] is None    # loop of none


def test_the_dispatched_epoch_gives_every_stage():
    from repro.configs.paper_models import make_mlp_problem
    from repro.data.pipeline import DeviceBatchStream, MixtureSpec
    from repro.optim.schedules import inverse_linear
    mix = MixtureSpec(n_classes=5, dim=16, sep=2.5)
    init, loss, _ = make_mlp_problem(dim=16, hidden=32, n_classes=5)
    pcfg = protocol.ProtocolConfig.derive(4, T=2, f_workers=1, f_servers=0,
                                          q_workers=3, q_servers=4)
    eng = protocol.ProtocolEngine(protocol.ProblemBundle(init=init, loss=loss),
                                  pcfg, inverse_linear(0.05, 0.01))
    state = eng.init_state(jax.random.PRNGKey(0))
    eng.run_epoch(state, DeviceBatchStream(0, mix, 4, 8).next(2))
    assert set(stages.dispatched_stages().values()) >= set(protocol.STAGES)
