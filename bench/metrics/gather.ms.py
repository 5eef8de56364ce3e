"""gather.ms (ms): device time per training step of the ops the program built
in its ``gather`` stage, the DMC gather: the masked Median across the G
server replicas, run every T steps. The traced window's gather time (its ops
clipped to the window, averaged over the chips) divided by all of the
window's steps, not by the gathers (``benchlib.stages``). Layer: the ByzSGD
step. Moves ``tokens_per_s``. None where the program names no stages or no
gather ran in the window."""
from benchlib import stages


def read(run):
    return stages.stage_ms(run, "gather")
