"""rwkv6.step.mfu (%): the whole ByzSGD step's model FLOP utilization in an
RWKV6 cell: forward and backward operations per token
(``benchlib.flops_rwkv6``: matrix products and the WKV recurrence, no remat,
no exchange) times the tokens the traced window trained, over the window's
seconds, the chips and each chip's bf16 peak. Moves ``tokens_per_s``. None
for a configuration whose reference is not ``rwkv6``."""
from benchlib.flops_rwkv6 import model_flops_per_token


def read(run):
    if run.config.get("reference") != "rwkv6":
        return None
    rate = run.tokens / run.trace.window_s
    return (100.0 * model_flops_per_token(run.config) * rate
            / (len(run.chips) * run.peaks["flops_bf16"]))
