"""The masked Median kernel's readers, ``median_roofline`` and
``median.kernel_share``, on hand-made op lists: a pull and a gather call of
the kernel among other ops of their stages."""
from __future__ import annotations

import _bench_tiny  # noqa: F401  (puts bench/ on the path)
import pytest

from benchlib import spec, trace
from benchlib.peaks import peaks

PEAKS = peaks("TPU v5 lite")
PULL = ("%masked_median.3 = bf16[4,6144,8192]{2,1,0:T(8,128)(2,1)} "
        "custom-call(s32[16]{0} %reshape.7, f32[4,6144,8192]{2,1,0:T(8,128)}"
        " %bitcast), custom_call_target=\"tpu_custom_call\"")
GATHER = ("%masked_median.9 = f32[4,2,3072]{2,1,0:T(2,128)} custom-call("
          "s32[16]{0} %reshape.3, f32[4,2,3072]{2,1,0:T(2,128)} %p.2), "
          "custom_call_target=\"tpu_custom_call\"")
PULL_BYTES = 4 * 6144 * 8192 * (4 + 2)
GATHER_BYTES = 4 * 2 * 3072 * (4 + 4)


class Run:
    def __init__(self, ops, stage_by):
        self.trace = trace.Trace(window=(0, 10**9), ops={0: ops},
                                 shift={0: 0.0})
        self.chips, self.peaks, self.stage_by = [0], PEAKS, stage_by


def op(name, text, start, ns):
    return trace.Op(name, trace.parse_instruction(text)[2] if text else
                    "fusion", start, start + ns, text)


def ns_at_peak(nbytes):
    return nbytes / PEAKS["hbm_bytes_per_s"] * 1e9


def kernel_run(slowdown=1.0):
    """A pull call and a gather call, each ``slowdown`` times its least
    time, among a cast in the pull, a fusion in the gather and a worker op
    (each 1 ms)."""
    ops = [op("masked_median.3", PULL, 0, slowdown * ns_at_peak(PULL_BYTES)),
           op("convert.1", "", 2e7, 1e6),
           op("masked_median.9", GATHER, 3e7,
              slowdown * ns_at_peak(GATHER_BYTES)),
           op("fusion.2", "", 4e7, 1e6),
           op("fusion.7", "", 5e7, 1e6)]
    return Run(ops, {"masked_median.3": "pull", "convert.1": "pull",
                     "masked_median.9": "gather", "fusion.2": "gather",
                     "fusion.7": "worker_grad"})


def read(name, run):
    return spec.metric_reader(name).read(run)


def test_needed_bytes_are_the_senders_and_the_views():
    m = spec.metric_reader("median_roofline")
    assert m.needed_bytes(op("a", PULL, 0, 1)) == PULL_BYTES
    assert m.needed_bytes(op("b", GATHER, 0, 1)) == GATHER_BYTES
    assert m.nbytes("(bf16[2,3]{1,0}, f32[4]{0})") == 2 * 6 + 4 * 4


@pytest.mark.parametrize("slowdown", [1.0, 2.0, 4.0])
def test_roofline_is_least_time_over_time(slowdown):
    """A call that moves exactly its needed bytes at the peak reads 100%,
    never more; one that takes k times as long reads 100/k."""
    got = read("median_roofline", kernel_run(slowdown))
    assert got == pytest.approx(100.0 / slowdown, rel=1e-9)
    assert got <= 100.0 + 1e-9


def test_kernel_share_is_the_kernel_in_pull_and_gather():
    run = kernel_run(2.0)
    kernel = 2.0 * ns_at_peak(PULL_BYTES + GATHER_BYTES)
    got = read("median.kernel_share", run)
    assert got == pytest.approx(100.0 * kernel / (kernel + 2e6), rel=1e-9)


def test_no_kernel_call():
    run = Run([op("fusion.1", "", 0, 1e6), op("fusion.2", "", 2e6, 1e6)],
              {"fusion.1": "pull", "fusion.2": "gather"})
    assert read("median_roofline", run) is None
    assert read("median.kernel_share", run) == 0.0


def test_kernel_share_none_without_median_stages():
    run = Run([op("fusion.1", "", 0, 1e6)], {"fusion.1": "worker_grad"})
    assert read("median.kernel_share", run) is None
