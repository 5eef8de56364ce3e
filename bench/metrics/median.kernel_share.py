"""median.kernel_share (%): the share of the masked Median's device time
that the fused kernel takes: the device time of the kernel's calls (as
``median_roofline`` finds them) over the device time of every op the
program built in its ``pull`` and ``gather`` stages, over the traced window
(ops clipped to it) and the chips. Each op's stage comes from the run where
it carries them, else from the program the process dispatched last
(``benchlib.stages``). It reads what part of the Median took the kernel
route. Layer: the masked Median kernel. Moves ``tokens_per_s``. None where
the program names no stages or the two stages ran no op."""
from benchlib import spec, stages

MEDIAN_STAGES = ("pull", "gather")


def read(run):
    if not stages.STAGES:
        return None
    stage_by = getattr(run, "stage_by", None)
    if stage_by is None:
        stage_by = stages.dispatched_stages()
    is_call = spec.metric_reader("median_roofline").is_call
    kernel = total = 0.0
    for chip in run.chips:
        lo, hi = run.trace.device_window(chip)
        for op in run.trace.ops[chip]:
            d = (min(op.end, hi) - max(op.start, lo)) * 1e-9
            if d <= 0 or stage_by.get(op.name) not in MEDIAN_STAGES:
                continue
            total += d
            if is_call(op):
                kernel += d
    return 100.0 * kernel / total if total else None
