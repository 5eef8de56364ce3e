"""stage.unattributed_share (%): the share of the device's busy time in the
traced window spent in ops that no stage of the ByzSGD step holds: the key
split, the learning rate, the gather's predicate and the other programs the
window runs (``benchlib.stages``); busy time is the union of all ops, as
``device.idle_share`` reads it. Layer: the ByzSGD step. Moves
``tokens_per_s``: it bounds what the stage metrics leave unexplained. None
where the program names no stages."""
from benchlib import stages


def read(run):
    st = stages.of_run(run)
    if st is None or not st.busy_s:
        return None
    return 100.0 * st.unattributed_s / st.busy_s
