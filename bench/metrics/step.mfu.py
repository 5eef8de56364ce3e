"""step.mfu (%): the whole ByzSGD step's model FLOP utilization: forward and
backward operations per token (``benchlib.flops``, no remat, no exchange)
times the tokens the traced window trained, over the window's seconds, the
chips and each chip's bf16 peak. Moves ``tokens_per_s``, and bounds what a
kernel's roofline share can add to it."""
from benchlib.flops import model_flops_per_token


def read(run):
    per_token = model_flops_per_token(run.config, run.seq)
    rate = run.tokens / run.trace.window_s
    return 100.0 * per_token * rate / (len(run.chips) * run.peaks["flops_bf16"])
