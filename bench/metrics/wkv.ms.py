"""wkv.ms (ms): device time per training step of the ops the RWKV6 model
built under its ``wkv`` scope, the chunked WKV scan: forward, its
recomputation under remat, and backward, in every worker's gradient. Summed
over the traced window's ops (clipped to it), averaged over the chips,
divided by the window's steps (``benchlib.scopes``). Layer: the RWKV6 WKV
scan. Moves ``tokens_per_s``. None where no op of the scope ran (a dense
model, or a program that names no such scope)."""
from benchlib import scopes


def read(run):
    return scopes.scope_ms(run, "wkv")
