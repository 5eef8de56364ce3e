"""aggregate.ms (ms): device time per training step of the ops the program
built in its ``aggregate`` stage: the servers' aggregation of the chosen
gradients (``aggregate_gradients``). Summed over the traced window's ops
(clipped to it), averaged over the chips, divided by the window's steps
(``benchlib.stages``). Layer: the ByzSGD step. Moves ``tokens_per_s``. None
where the program names no stages or the stage ran no op."""
from benchlib import stages


def read(run):
    return stages.stage_ms(run, "aggregate")
