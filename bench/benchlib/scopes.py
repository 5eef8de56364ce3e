"""Device time of the ops the program built under a ``jax.named_scope`` of
its own inside a stage, such as the RWKV6 model's ``wkv``, read from a traced
run.

An op is in a scope where a component of its op_name path, stripped of
transform wrappers (``vmap(...)``, ``jvp(...)``, ``transpose(...)``,
``checkpoint``'s and ``remat``'s) as ``repro.core.protocol.stage_of``
strips them, is the scope's name: a forward op, its recomputation under
remat and its backward op all are. The op_names come from the compiled text
of the epoch the process dispatched last, as ``benchlib.stages`` reads
them; an instruction the compiler made without metadata takes its operands'
reading where they agree, else its computation's (every named instruction
of the computation in the scope, or none), else its caller's.
"""
from __future__ import annotations

import re

from benchlib import stages

# a transform's wrapper around a scope in an op_name path: vmap(jvp(wkv))
_WRAPPER = re.compile(r"[\w.<>]*\((.*)\)")


def in_scope(op_name: str, scope: str) -> bool:
    """True where ``scope`` is a component of the op_name's path (the last
    component is the op itself; after a ``;`` come fused ops' names)."""
    for part in op_name.split(";", 1)[0].split("/")[:-1]:
        while (m := _WRAPPER.fullmatch(part)):
            part = m.group(1)
        if part == scope:
            return True
    return False


def scoped_from_hlo(text: str, scope: str) -> set[str]:
    """Names of the instructions of an HLO module's text built in
    ``scope``."""
    tag, computation_of, caller, named = {}, {}, {}, {}
    computation = None
    for line in text.splitlines():
        if line and not line[0].isspace():
            if line.endswith("{"):           # a computation's header
                computation = line.split(" ", 2)[
                    1 if line.startswith("ENTRY ") else 0].lstrip("%")
            continue
        m = stages._HLO_INSTRUCTION.match(line)
        if not m:
            continue
        name = m.group(1)
        computation_of[name] = computation
        op = stages._HLO_OP_NAME.search(line)
        if op:
            tag[name] = in_scope(op.group(1), scope)
            named.setdefault(computation, set()).add(tag[name])
        else:
            ups = {tag.get(o) for o in stages._HLO_NAME.findall(line, m.end())
                   if computation_of.get(o) == computation} - {None}
            if len(ups) == 1:
                tag[name] = ups.pop()
        for called in stages._HLO_CALLS.findall(line):
            for c in stages._HLO_NAME.findall(called):
                caller.setdefault(c, name)

    def of_computation(c):
        tags = named.get(c, set())
        if len(tags) == 1:
            return next(iter(tags))
        up = caller.get(c)
        if up is None:
            return False
        return tag[up] if up in tag else of_computation(computation_of[up])

    by_computation = {c: of_computation(c) for c in set(
        computation_of.values())}
    return {name for name, c in computation_of.items()
            if (tag[name] if name in tag else by_computation[c])}


def dispatched_text() -> str | None:
    """The compiled text of the epoch the process dispatched last."""
    try:
        from repro.core.epochs import last_dispatched
    except ImportError:
        return None
    runner = last_dispatched()
    return None if runner is None else runner.lower().compile().as_text()


def scope_ms(run, scope: str) -> float | None:
    """Milliseconds of device time per training step of the window's ops in
    ``scope`` (clipped to the window, averaged over the chips); None where
    no such op ran. The compiled text is read once and kept on the run."""
    if not hasattr(run, "hlo_text"):
        run.hlo_text = dispatched_text()
    if not run.hlo_text:
        return None
    names = scoped_from_hlo(run.hlo_text, scope)
    total, seen = 0.0, False
    for chip in run.chips:
        lo, hi = run.trace.device_window(chip)
        for op in run.trace.ops[chip]:
            if op.name not in names:
                continue
            seen = True
            total += max(0.0, min(op.end, hi) - max(op.start, lo)) * 1e-9
    if not seen:
        return None
    return 1e3 * total / (len(run.chips) * run.steps)
