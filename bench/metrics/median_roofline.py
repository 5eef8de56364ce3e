"""median_roofline (%): the masked Median kernel's share of its roofline.
For every call of the kernel (``repro.kernels.cwise_median``
``masked_median``, one per leaf in the pull and in the DMC gather) in the
traced window: the least time the chip could take, its needed bytes over
the HBM peak, where the needed bytes are the sender operand's (each replica
read once) and the result's (each receiver's view written once), read from
the call's shapes in its instruction text. Summed, and divided by the
calls' summed device time. Memory bounds the kernel: its few compare and
select operations per coordinate take a fraction of the bytes' time.
Layer: the masked Median kernel. Moves ``tokens_per_s``. None where the
window ran no such call."""
import math
import re

from benchlib.trace import parse_instruction, shape_of, split_top

ITEMSIZE = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s16": 2,
            "u16": 2, "f32": 4, "s32": 4, "u32": 4, "f64": 8, "s64": 8,
            "u64": 8}


def is_call(op) -> bool:
    """Whether a trace op is a call of the masked Median kernel."""
    return op.opcode == "custom-call" and "masked_median" in op.name


def nbytes(typ: str) -> int:
    """Bytes of an array type such as ``bf16[4,6144,8192]{2,1,0:T(8,128)}``;
    a tuple type sums its parts."""
    if typ.startswith("("):
        return sum(nbytes(t) for t in split_top(typ[1:-1]))
    dtype = re.match(r"\w+", typ).group(0)
    return ITEMSIZE[dtype] * math.prod(shape_of(typ))


def needed_bytes(op) -> int:
    """The sender stack read (the call's last operand; the first holds the
    delivery masks) and the views written."""
    _, typ, _, operands = parse_instruction(op.text)
    return nbytes(operands[-1]) + nbytes(typ)


def read(run):
    need = took = 0.0
    for chip in run.chips:
        for op in run.trace.ops[chip]:
            if is_call(op):
                need += needed_bytes(op) / run.peaks["hbm_bytes_per_s"]
                took += (op.end - op.start) * 1e-9
    return 100.0 * need / took if took else None
