"""Reduction of a profiler trace (``.xplane.pb``) to what the metrics read.

Layout of a TPU trace as JAX 0.9 writes it: one plane per chip,
``/device:TPU:<n>``, whose line ``XLA Ops`` holds one event per HLO
instruction run, named by the instruction's text (``%name = type opcode(
operands), attributes``), control flow (``while``, ``conditional``) as
events that span the events of their bodies, which are left out here; line ``XLA Modules`` holds one event per program
run with its ``run_id``. The plane ``/host:CPU`` holds the host threads:
the harness's own spans (``bench/...``, from ``TraceAnnotation``) and the
runtime's ``DoEnqueueProgram`` (with ``run_id``), which lets the device
timeline be placed on the host's: a program cannot start before the host
enqueued it, so each chip's events are shifted by the least amount that
puts every program after its enqueue. That shift only names idle gaps; busy
time and op time are read on the device's own clock.
"""
from __future__ import annotations

import re
import warnings
from dataclasses import dataclass, field

SPAN_PREFIX = "bench/"
COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter",
               "collective-permute", "all-to-all", "send", "recv")
# control flow: such an event spans the ops of its body, which have events
# of their own, so it is left out of every sum
CONTAINERS = ("while", "conditional", "call")


@dataclass(frozen=True)
class Op:
    name: str
    opcode: str
    start: float        # ns, device clock
    end: float
    text: str

    @property
    def is_collective(self) -> bool:
        return self.opcode.startswith(COLLECTIVES)

    @property
    def is_container(self) -> bool:
        return self.opcode in CONTAINERS


@dataclass
class Trace:
    window: tuple[float, float]                 # host ns: the traced window
    ops: dict[int, list[Op]] = field(default_factory=dict)   # per chip
    shift: dict[int, float] = field(default_factory=dict)    # device->host
    spans: list[tuple[str, float, float]] = field(default_factory=list)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def busy_s(self, chip: int) -> float:
        lo, hi = self.device_window(chip)
        return _length(_clip(_union(self.ops[chip]), lo, hi)) * 1e-9

    def device_window(self, chip: int) -> tuple[float, float]:
        s = self.shift.get(chip, 0.0)
        return self.window[0] - s, self.window[1] - s

    def idle_gaps(self, chip: int) -> list[tuple[str, float, float]]:
        """Gaps in the window with no op on ``chip``, each named by the
        innermost harness span around its midpoint (host clock)."""
        lo, hi = self.device_window(chip)
        s = self.shift.get(chip, 0.0)
        busy = _clip(_union(self.ops[chip]), lo, hi)
        out, cur = [], lo
        for a, b in busy + [(hi, hi)]:
            if a > cur:
                mid = (a + cur) / 2 + s
                around = [sp for sp in self.spans if sp[1] <= mid <= sp[2]]
                name = (min(around, key=lambda sp: sp[2] - sp[1])[0]
                        if around else "outside the harness's spans")
                out.append((name, cur + s, a + s))
            cur = max(cur, b)
        return out

    def exposed_collective_s(self, chip: int) -> float | None:
        """Time in collective ops on ``chip`` during which no other op runs
        there; None when the chip ran no collective."""
        lo, hi = self.device_window(chip)
        coll = [o for o in self.ops[chip] if o.is_collective]
        if not coll:
            return None
        other = _union([o for o in self.ops[chip] if not o.is_collective])
        c = _clip(_union(coll), lo, hi)
        return (_length(c) - _length(_intersect(c, other))) * 1e-9

    def top_ops(self, n: int = 10) -> list[list]:
        """The ``n`` instructions with the most device time, summed over the
        chips and averaged over them: ``[label, seconds]``."""
        tot: dict[str, float] = {}
        for chip, ops in self.ops.items():
            lo, hi = self.device_window(chip)
            for o in ops:
                d = min(o.end, hi) - max(o.start, lo)
                if d > 0:
                    label = _label(o)
                    tot[label] = tot.get(label, 0.0) + d * 1e-9
        k = max(len(self.ops), 1)
        return [[name, sec / k] for name, sec in
                sorted(tot.items(), key=lambda kv: -kv[1])[:n]]

    def top_gaps(self, chip: int, n: int = 10) -> list[list]:
        """Idle time on ``chip`` summed by what the host was doing:
        ``[label, seconds]``, the label giving the count and the longest."""
        by: dict[str, list[float]] = {}
        for name, a, b in self.idle_gaps(chip):
            by.setdefault(name, []).append((b - a) * 1e-9)
        rows = sorted(by.items(), key=lambda kv: -sum(kv[1]))[:n]
        return [[f"{name}: {len(g)} gaps, longest {max(g):.6f} s", sum(g)]
                for name, g in rows]


def _label(o: Op) -> str:
    return f"{o.name} {o.opcode} {_out_type(o.text)}"[:120]


def _union(ops) -> list[tuple[float, float]]:
    iv = sorted((o.start, o.end) if isinstance(o, Op) else o for o in ops)
    out: list[list[float]] = []
    for a, b in iv:
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(iv, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in iv if b > lo and a < hi]


def _length(iv) -> float:
    return sum(b - a for a, b in iv)


def _intersect(x, y) -> list[tuple[float, float]]:
    out, i, j = [], 0, 0
    while i < len(x) and j < len(y):
        a, b = max(x[i][0], y[j][0]), min(x[i][1], y[j][1])
        if a < b:
            out.append((a, b))
        if x[i][1] < y[j][1]:
            i += 1
        else:
            j += 1
    return out


# -- HLO instruction text ----------------------------------------------------

def split_top(s: str, sep: str = ",") -> list[str]:
    """Split ``s`` at ``sep`` outside brackets, braces and parentheses."""
    out, depth, cur = [], 0, []
    for ch in s:
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
        if ch == sep and depth == 0:
            out.append("".join(cur).strip())
            cur = []
        else:
            cur.append(ch)
    if "".join(cur).strip():
        out.append("".join(cur).strip())
    return out


def _matching(s: str, i: int) -> int:
    """Index just past the bracket group that opens at ``s[i]``."""
    depth = 0
    for j in range(i, len(s)):
        if s[j] in "([{":
            depth += 1
        elif s[j] in ")]}":
            depth -= 1
            if depth == 0:
                return j + 1
    return len(s)


def parse_instruction(text: str) -> tuple[str, str, str, list[str]]:
    """``%name = type opcode(operands), ...`` -> (name, type, opcode,
    operands). Unparsable text gives the text as the name."""
    m = re.match(r"%?(\S+) = ", text)
    if not m:
        return text, "", "", []
    rest = text[m.end():]
    end = _matching(rest, 0) if rest.startswith("(") else rest.find(" ")
    if end < 0:
        return m.group(1), rest, "", []
    typ, rest = rest[:end], rest[end:].lstrip()
    paren = rest.find("(")
    if paren < 0:
        return m.group(1), typ, rest, []
    opcode = rest[:paren]
    operands = split_top(rest[paren + 1:_matching(rest, paren) - 1])
    return m.group(1), typ, opcode, operands


def _out_type(text: str) -> str:
    return parse_instruction(text)[1]


def shape_of(typ: str) -> tuple[int, ...]:
    """``bf16[96,2048,128]{...}`` -> (96, 2048, 128)."""
    m = re.search(r"\[([\d,]*)\]", typ)
    return tuple(int(x) for x in m.group(1).split(",") if x) if m else ()


# -- loading -----------------------------------------------------------------

def _stats(ev) -> dict:
    # the profiler's stats type warns, on first use, that it has no module
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        return dict(ev.stats)


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    spans, enqueue = [], {}
    ops: dict[int, list[Op]] = {}
    modules: dict[int, dict[int, float]] = {}
    for plane in data.planes:
        m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        if m:
            chip = int(m.group(1))
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops[chip] = [
                        op for op in (Op(parse_instruction(e.name)[0],
                                         parse_instruction(e.name)[2],
                                         e.start_ns,
                                         e.start_ns + e.duration_ns, e.name)
                                      for e in line.events)
                        if not op.is_container]
                elif line.name == "XLA Modules":
                    modules[chip] = {int(_stats(e).get("run_id", -1)):
                                     e.start_ns for e in line.events}
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.name, e.start_ns,
                                      e.start_ns + e.duration_ns))
                    elif e.name == "DoEnqueueProgram":
                        st = _stats(e)
                        enqueue[(int(st.get("device_ordinal", 0)),
                                 int(st.get("run_id", -1)))] = e.start_ns
    dispatch = [s for s in spans if s[0] == SPAN_PREFIX + "dispatch_epoch"]
    if not dispatch or not ops:
        raise ValueError(f"{path}: no harness spans or no device ops")
    window = (min(s[1] for s in dispatch), max(s[2] for s in spans))
    shift = {}
    for chip, mods in modules.items():
        lags = [enqueue[(chip, rid)] - start for rid, start in mods.items()
                if (chip, rid) in enqueue]
        shift[chip] = max([0.0] + lags)
    for chip in ops:
        ops[chip].sort(key=lambda o: o.start)
    return Trace(window=window, ops=ops, shift=shift, spans=spans)
