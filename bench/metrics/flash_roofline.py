"""flash_roofline (%): the flash-attention kernels' share of their roofline.
For every call of the forward, dq and dkv kernels in the traced window: the
least time the chip could take, the larger of its needed operations over the
bf16 peak and its needed bytes over the HBM peak (``benchlib.flops``), summed
and divided by the calls' summed device time. The operations bound every
call at these shapes. None where the window ran no flash kernel."""
import math

from benchlib.flops import flash_call
from benchlib.trace import parse_instruction, shape_of, split_top


def kind_of(op):
    """'fwd', 'dq' or 'dkv' for a flash custom call, else None."""
    if op.opcode != "custom-call" or "flash_attention" not in op.name:
        return None
    _, typ, _, operands = parse_instruction(op.text)
    outs = split_top(typ[1:-1]) if typ.startswith("(") else [typ]
    if len(operands) == 3:
        return "fwd"
    if len(operands) == 6:
        return "dkv" if len(outs) == 2 else "dq"
    return None


def read(run):
    c = run.config
    H, kvH = c["num_attention_heads"], c["num_key_value_heads"]
    hd = c.get("head_dim") or c["hidden_size"] // H
    need = took = 0.0
    for chip in run.chips:
        for op in run.trace.ops[chip]:
            kind = kind_of(op)
            if kind is None:
                continue
            # q as [..., seq, hd_pad]: every leading dim counts heads (a
            # vmap over the groups adds one)
            heads = math.prod(shape_of(parse_instruction(op.text)[3][0])[:-2])
            flops, nbytes = flash_call(kind, heads, run.seq, hd, H // kvH)
            need += max(flops / run.peaks["flops_bf16"],
                        nbytes / run.peaks["hbm_bytes_per_s"])
            took += (op.end - op.start) * 1e-9
    return 100.0 * need / took if took else None
