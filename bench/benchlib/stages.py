"""Device time of the ByzSGD step's stages, read from a traced run.

The program builds each stage of its step under a ``jax.named_scope``
(``repro.core.protocol.STAGES``), which puts the stage into the op_name
metadata of the HLO instructions; ``protocol.stage_of`` reads it back. A
trace's device events are named by the instruction's text alone. Two routes
give each event its stage:

- the compiled program: the epoch the process dispatched last
  (``repro.core.epochs.last_dispatched``), lowered again and compiled
  (served by the compile caches), and the metadata of its text
  (:func:`stages_from_hlo`), keyed by the instruction's name. The metric
  readers take this route: ``bench/run.py`` deletes the trace file before it
  calls them. The text also holds the instructions the compiler made without
  metadata, and where in the program each one sits.
- the ``.xplane.pb`` itself: each event's metadata carries the op_name as the
  stat ``tf_op``, which ``jax.profiler.ProfileData`` does not expose; a small
  reader of the protobuf wire format gets it (:func:`op_names_from_xplane`).
  Events made without metadata have none.

A program that names no stages has no ``STAGES``: every reader of this
module then reads None.
"""
from __future__ import annotations

import re
from dataclasses import dataclass

from benchlib import trace as trace_mod

try:
    from repro.core.protocol import STAGES, stage_of
except ImportError:          # a program from before the stages were named
    STAGES, stage_of = (), None

SPAN_PREFIX = "repro/"


@dataclass(frozen=True)
class StageTimes:
    """Device seconds per training step, averaged over the chips, of the
    window's ops: by stage (a stage that ran no op is left out), of the ops
    in no stage, and the busy time (the union of all ops)."""
    stage_s: dict
    unattributed_s: float
    busy_s: float


def stage_times(tr, chips, steps: int, stage_by: dict) -> StageTimes:
    """Sum each op's device time inside the window by its stage, looked up
    in ``stage_by`` by the op's instruction name."""
    total = {}
    none = 0.0
    for chip in chips:
        lo, hi = tr.device_window(chip)
        for op in tr.ops[chip]:
            d = (min(op.end, hi) - max(op.start, lo)) * 1e-9
            if d <= 0:
                continue
            stage = stage_by.get(op.name)
            if stage is None:
                none += d
            else:
                total[stage] = total.get(stage, 0.0) + d
    k = len(chips) * steps
    busy = sum(tr.busy_s(c) for c in chips)
    return StageTimes({s: v / k for s, v in total.items()}, none / k,
                      busy / k)


def of_run(run) -> StageTimes | None:
    """The stage times of a traced run, read once and kept on it. The ops'
    stages come from ``run.stage_by`` where the run carries them, else from
    the program the process dispatched last."""
    if not STAGES:
        return None
    if not hasattr(run, "_stage_times"):
        stage_by = getattr(run, "stage_by", None)
        if stage_by is None:
            stage_by = dispatched_stages()
        run._stage_times = (stage_times(run.trace, run.chips, run.steps,
                                        stage_by) if stage_by else None)
    return run._stage_times


def stage_ms(run, stage: str) -> float | None:
    """Milliseconds of device time per training step in ``stage``; None
    where the stage ran no op."""
    st = of_run(run)
    if st is None or stage not in st.stage_s:
        return None
    return 1e3 * st.stage_s[stage]


# -- the op names ------------------------------------------------------------

_HLO_INSTRUCTION = re.compile(r"\s+(?:ROOT\s+)?%?([\w.\-]+) = ")
_HLO_NAME = re.compile(r"%([\w.\-]+)")
_HLO_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_HLO_CALLS = re.compile(r"\b(?:calls|body|condition|to_apply|"
                        r"branch_computations)=(\{[^}]*\}|%[\w.\-]+)")


def stages_from_hlo(text: str) -> dict[str, str | None]:
    """Instruction name -> stage, from an HLO module's text. An instruction
    with op_name metadata is in the stage it names. One the compiler made
    without metadata (a loop counter's copy, a fusion that writes a chunk
    into a buffer, an op split off inside a loop or a branch) is in the one
    stage its operands are in, where they name one and no other; else in
    the one stage its computation's instructions name; else in the stage of
    the instruction that calls its computation. The text lists operands
    before their users, and a computation's callers after it."""
    stage, computation_of, caller, named = {}, {}, {}, {}
    computation = None
    for line in text.splitlines():
        if line and not line[0].isspace():
            if line.endswith("{"):           # a computation's header
                computation = line.split(" ", 2)[
                    1 if line.startswith("ENTRY ") else 0].lstrip("%")
            continue
        m = _HLO_INSTRUCTION.match(line)
        if not m:
            continue
        name = m.group(1)
        computation_of[name] = computation
        op = _HLO_OP_NAME.search(line)
        if op:
            stage[name] = stage_of(op.group(1))
            if stage[name] is not None:
                named.setdefault(computation, set()).add(stage[name])
        else:
            ups = {stage.get(o) for o in _HLO_NAME.findall(line, m.end())
                   if computation_of.get(o) == computation} - {None}
            if len(ups) == 1:
                stage[name] = ups.pop()
        for called in _HLO_CALLS.findall(line):
            for c in _HLO_NAME.findall(called):
                caller.setdefault(c, name)

    def of_computation(c):
        stages = named.get(c, set())
        if len(stages) == 1:
            return next(iter(stages))
        up = caller.get(c)
        if up is None:
            return None
        return stage[up] if up in stage else of_computation(
            computation_of[up])

    by_computation = {c: of_computation(c) for c in set(
        computation_of.values())}
    return {name: stage[name] if name in stage else by_computation[c]
            for name, c in computation_of.items()}


def dispatched_stages() -> dict[str, str | None]:
    """The stages of the instructions of the epoch the process dispatched
    last; empty where there is none."""
    try:
        from repro.core.epochs import last_dispatched
    except ImportError:
        return {}
    runner = last_dispatched()
    if runner is None:
        return {}
    return stages_from_hlo(runner.lower().compile().as_text())


def _varint(b, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        c = b[i]
        i += 1
        out |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return out, i


def _fields(b):
    """(field number, value) of a protobuf message: an int for a varint,
    bytes for a length-delimited or fixed-width field."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            size, i = _varint(b, i)
            v, i = b[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            v, i = b[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield key >> 3, v


def _entry(b) -> tuple[int, bytes]:
    """A protobuf map entry: key (1) and value (2)."""
    f = dict(_fields(b))
    return f.get(1, 0), f.get(2, b"")


def op_names_from_xplane(path: str) -> dict[str, str]:
    """Event text -> op_name of the device ops in a ``.xplane.pb``, from the
    stat ``tf_op`` of each event's metadata (an event without it is left
    out).

    XSpace: planes (1). XPlane: name (2), event_metadata (4, map of id to
    XEventMetadata), stat_metadata (5, map of id to XStatMetadata).
    XEventMetadata: name (2), stats (5). XStatMetadata: name (2). XStat:
    metadata_id (1), str_value (5) or ref_value (7, the id of a stat
    metadata whose name is the string)."""
    with open(path, "rb") as fh:
        data = memoryview(fh.read())
    out = {}
    for f, plane in _fields(data):
        if f != 1:
            continue
        name, events, stat_names = "", [], {}
        for pf, v in _fields(plane):
            if pf == 2:
                name = bytes(v).decode()
            elif pf == 4:
                events.append(_entry(v)[1])
            elif pf == 5:
                sid, md = _entry(v)
                stat_names[sid] = bytes(dict(_fields(md)).get(2, b"")
                                        ).decode()
        if not name.startswith("/device:"):
            continue
        for md in events:
            text, op = "", None
            for ef, v in _fields(md):
                if ef == 2:
                    text = bytes(v).decode()
                elif ef == 5:
                    st = dict(_fields(v))
                    if stat_names.get(st.get(1)) != "tf_op":
                        continue
                    op = (bytes(st[5]).decode() if 5 in st
                          else stat_names.get(st.get(7), ""))
            if op:
                out[text] = op
    return out


def load(path: str):
    """A trace read as ``benchlib.trace.load`` reads it, with the program's
    own spans (``repro/...``) added to the harness's, and the stages of its
    device ops that carry an op_name, by instruction name:
    ``(trace, stage_by)``. The window stays the one the harness's spans
    define; the program's spans, inside them, only name the idle gaps they
    hold."""
    from jax.profiler import ProfileData
    tr = trace_mod.load(path)
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                tr.spans.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                                for e in line.events
                                if e.name.startswith(SPAN_PREFIX))
    return tr, {trace_mod.parse_instruction(text)[0]: stage_of(op)
                for text, op in op_names_from_xplane(path).items()}
