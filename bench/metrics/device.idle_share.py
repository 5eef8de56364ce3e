"""device.idle_share (%): the share of the traced window in which no
operation ran on the device, averaged over the chips the cell uses. Layer:
the epoch engine's host dispatch loop. Moves ``tokens_per_s``: time the chip
waits for the host is time it trains nothing."""


def read(run):
    busy = sum(run.trace.busy_s(c) for c in run.chips) / len(run.chips)
    return 100.0 * (1.0 - busy / run.trace.window_s)
