"""The ByzSGD step names its stages: every stage in ``protocol.STAGES`` is a
``jax.named_scope`` that reaches the op_name metadata of the compiled epoch
(inside the scan, the gather's ``cond``, ``vmap(grad)``), and no op of the
step's body that makes a parameter-sized array is left outside a stage."""
import re

import jax
import pytest

from repro.configs.paper_models import make_mlp_problem
from repro.core import epochs, protocol
from repro.core.attacks import ByzantineSpec
from repro.data.pipeline import DeviceBatchStream, MixtureSpec
from repro.optim.schedules import inverse_linear

MIX = MixtureSpec(n_classes=5, dim=16, sep=2.5)
G, T = 4, 2
# instructions that move no data of their own: they carry no op of a stage
PLUMBING = {"parameter", "get-tuple-element", "tuple", "while", "conditional",
            "call", "constant", "bitcast"}
LINE = re.compile(r"\s*(?:ROOT\s+)?%?([\w.\-]+) = (\S+) ([\w\-]+)\(")
COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+) \(.*\{$")


def compiled_epoch(pull="median", with_attack=False):
    init, loss, _ = make_mlp_problem(dim=MIX.dim, hidden=32,
                                     n_classes=MIX.n_classes)
    byz = (ByzantineSpec(worker_attack="reversed", server_attack="reversed",
                         n_byz_workers=1, n_byz_servers=1)
           if with_attack else None)
    pcfg = protocol.ProtocolConfig.derive(
        G, T=T, pull=pull, byz=byz, f_workers=1, f_servers=int(with_attack),
        q_workers=3, q_servers=4)
    eng = protocol.ProtocolEngine(protocol.ProblemBundle(init=init, loss=loss),
                                  pcfg, inverse_linear(0.05, 0.01),
                                  with_attack=with_attack)
    state = eng.init_state(jax.random.PRNGKey(0))
    state, _ = eng.run_epoch(state, DeviceBatchStream(0, MIX, G, 8).next(T))
    assert epochs.last_dispatched() is eng
    return state, eng.lower().compile().as_text()


def instructions(text):
    """(name, type, opcode, op_name) of every instruction outside the
    computations fusions call (their ops run inside the fusion's)."""
    fused = set(re.findall(r"\bfusion\(.*?calls=%?([\w.\-]+)", text))
    out, comp = [], None
    for line in text.splitlines():
        m = COMPUTATION.match(line)
        if m:
            comp = m.group(1)
            continue
        m = LINE.match(line)
        if m and comp not in fused:
            op = re.search(r'op_name="([^"]*)"', line)
            out.append((*m.groups(), op.group(1) if op else ""))
    return out


@pytest.fixture(scope="module", params=[
    ("median", False), ("roundrobin", False), ("median", True)],
    ids=["median", "roundrobin", "attack"])
def compiled(request):
    return compiled_epoch(*request.param)


def test_every_stage_reaches_the_compiled_epoch(compiled):
    _, text = compiled
    named = {protocol.stage_of(op) for *_, op in instructions(text)}
    assert set(protocol.STAGES) <= named


def test_no_parameter_sized_op_of_the_step_is_unattributed(compiled):
    state, text = compiled
    sizes = {tuple(leaf.shape) for leaf in jax.tree.leaves(state.params)
             if leaf.ndim >= 3}                     # [G, ...] weight matrices
    loose = []
    for name, typ, opcode, op in instructions(text):
        shapes = {tuple(int(d) for d in dims.split(",") if d)
                  for dims in re.findall(r"\[([\d,]*)\]", typ)}
        if (opcode not in PLUMBING and shapes & sizes
                and op.startswith("jit(epoch)/while/body/")
                and protocol.stage_of(op) is None):
            loose.append((name, opcode, op))
    assert not loose


@pytest.mark.parametrize("op_name, stage", [
    ("jit(epoch)/while/body/closed_call/worker_grad/"
     "vmap(transpose(jvp(worker_grad)))/checkpoint/transpose", "worker_grad"),
    ("jit(epoch)/while/body/closed_call/cond/branch_1_fun/gather/"
     "jit(median)/sort", "gather"),
    ("jit(epoch)/while/body/closed_call/vmap(jvp(pull))/sort:", "pull"),
    ("jit(epoch)/while/body/closed_call/distances/dot_general;"
     "aggregate/mul", "distances"),
    # a primitive named like a stage is the op, not a scope
    ("jit(epoch)/while/body/gather", None),
    ("jit(epoch)/while/body/closed_call/jit(_threefry_split)/add", None),
    ("", None),
])
def test_stage_of_reads_the_outermost_stage_scope(op_name, stage):
    assert protocol.stage_of(op_name) == stage
