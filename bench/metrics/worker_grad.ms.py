"""worker_grad.ms (ms): device time per training step of the ops the program
built in its ``worker_grad`` stage: the G workers' forward and backward
passes (``vmap(grad)``, the micro-batch scan), the cast to the exchange
dtype and the gradient attack. Summed over the traced window's ops (clipped
to it), averaged over the chips, divided by the window's steps
(``benchlib.stages``). Layer: the ByzSGD step. Moves ``tokens_per_s``. None
where the program names no stages or the stage ran no op."""
from benchlib import stages


def read(run):
    return stages.stage_ms(run, "worker_grad")
