"""Post-SPMD HLO analysis: collective traffic + donation auditing.

Collective bytes use a ring model on the *per-device* (post-partitioning)
shapes that appear in ``compiled.as_text()``:

    all-gather:          (g-1)/g * output_local_bytes
    all-reduce:          2 (g-1)/g * operand_local_bytes
    reduce-scatter:      (g-1)/g * operand_local_bytes
    all-to-all:          (g-1)/g * operand_local_bytes
    collective-permute:  operand_local_bytes

(g = replica-group size). Summing per-device traffic and dividing by the
per-chip link bandwidth is algebraically the spec's
``collective_bytes / (chips * link_bw)`` with collective_bytes = total traffic.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16, "u4": 1, "s4": 1,
}

_SHAPE_RE = re.compile(r"\b([a-z0-9]+)\[([0-9,]*)\]")
_COLL_RE = re.compile(
    r"=\s*(?:\([^)]*\)|\S+)\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")
_GROUPS_RE = re.compile(r"replica_groups=\{\{([0-9,]+)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    if dtype not in _DTYPE_BYTES:
        return 0
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES[dtype]


def _line_shapes(line: str):
    """(result_bytes, operand_bytes) from one HLO instruction line."""
    eq = line.find("=")
    op_start = line.find("(", eq)
    res = sum(_shape_bytes(d, s) for d, s in _SHAPE_RE.findall(line[:op_start]))
    # operands: shapes inside the call parens, before attribute list
    tail = line[op_start:]
    cut = tail.find("), ")
    operand_str = tail[: cut + 1 if cut >= 0 else len(tail)]
    ops = sum(_shape_bytes(d, s) for d, s in _SHAPE_RE.findall(operand_str))
    return res, ops


def _group_size(line: str, default: int) -> int:
    m = _GROUPS_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        return int(m.group(2))
    return default


@dataclass
class CollectiveStats:
    bytes_per_device: float = 0.0
    counts: dict = field(default_factory=dict)
    bytes_by_kind: dict = field(default_factory=dict)
    bytes_by_group_size: dict = field(default_factory=dict)

    def add(self, kind, b, g=None):
        self.bytes_per_device += b
        self.counts[kind] = self.counts.get(kind, 0) + 1
        self.bytes_by_kind[kind] = self.bytes_by_kind.get(kind, 0.0) + b
        if g is not None:
            key = str(g)
            self.bytes_by_group_size[key] =                 self.bytes_by_group_size.get(key, 0.0) + b


def collective_traffic(hlo_text: str, n_devices: int) -> CollectiveStats:
    """Per-device ring-model collective bytes from post-SPMD HLO text."""
    stats = CollectiveStats()
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        kind = m.group(1)
        res_b, op_b = _line_shapes(line)
        g = _group_size(line, n_devices)
        if g <= 1:
            continue
        ring = (g - 1) / g
        if kind == "all-gather":
            b = ring * res_b
        elif kind == "all-reduce":
            b = 2.0 * ring * op_b
        elif kind == "reduce-scatter":
            b = ring * op_b
        elif kind == "all-to-all":
            b = ring * op_b
        else:  # collective-permute
            b = float(op_b)
        stats.add(kind, b, g)
    return stats


# ---------------------------------------------------------------------------
# donation / buffer-alias auditing (repro.analyze layer 2)
# ---------------------------------------------------------------------------

# compiled.as_text() header entry:
#   input_output_alias={ {0}: (0, {}, may-alias), {1}: (2, {}, must-alias) }
# one `{output_index}: (param_number, param_index, kind)` entry per aliased
# buffer. jax's donate_argnums lowers each donated pytree leaf to one entry
# (CPU included — donation there is may-alias, but the alias table is still
# emitted, which is what makes this statically checkable off-accelerator).
_ALIAS_ENTRY_RE = re.compile(
    r"\{(?P<out>[0-9,\s]*)\}:\s*\(\s*(?P<param>\d+)\s*,\s*"
    r"\{(?P<pidx>[0-9,\s]*)\}\s*,\s*(?P<kind>may-alias|must-alias)\s*\)")


@dataclass(frozen=True)
class AliasEntry:
    output_index: tuple
    param_number: int
    param_index: tuple
    kind: str


def donation_aliases(hlo_text: str) -> list[AliasEntry]:
    """Parse the ``input_output_alias`` table of compiled HLO text.

    Returns one :class:`AliasEntry` per aliased (donated) buffer; an empty
    list means XLA dropped every donation — the repo's donated-scan engines
    treat that as a violation (REPRO-HLO-DONATION)."""
    start = hlo_text.find("input_output_alias={")
    if start < 0:
        return []
    # the table is brace-nested: scan to the balanced close
    i = hlo_text.find("{", start)
    depth, j = 0, i
    while j < len(hlo_text):
        if hlo_text[j] == "{":
            depth += 1
        elif hlo_text[j] == "}":
            depth -= 1
            if depth == 0:
                break
        j += 1
    block = hlo_text[i:j + 1]
    out = []
    for m in _ALIAS_ENTRY_RE.finditer(block):
        def _tup(s):
            return tuple(int(p) for p in s.split(",") if p.strip())
        out.append(AliasEntry(_tup(m.group("out")), int(m.group("param")),
                              _tup(m.group("pidx")), m.group("kind")))
    return out


def aliased_param_numbers(hlo_text: str) -> set[int]:
    """Parameter numbers covered by the input_output_alias table."""
    return {e.param_number for e in donation_aliases(hlo_text)}
