"""``metrics/spmm_lane_fill.py`` on hand-built counter snapshots."""
import pytest

from chipbench import harness
from repro.obs import metrics as obs_metrics
from repro.obs import reset_metrics

TRACED = {"trace": {"op_seconds": {"paramspmm_v2_k56_r32_d256": 1.5,
                                   "fusion": 0.1}}}


def _counter(kernel, moved, carried):
    lanes = obs_metrics.counter("kernel_gather_lanes_total")
    lanes.inc(moved, kernel=kernel, lanes="moved")
    lanes.inc(carried, kernel=kernel, lanes="carried")


def test_carried_over_moved_of_the_spmm_kernels():
    reset_metrics()
    _counter("paramspmm_v2_k56_r32_d256", 5 * 256, 128 + 256 + 40)
    _counter("paramspmm_v2_k56_r32_d256_relu", 256, 256)
    _counter("sddmm_v2_k48_w16_d128", 128, 8)      # not an SpMM kernel
    got = harness.reader("spmm_lane_fill")(TRACED)
    assert got == pytest.approx(100.0 * (128 + 256 + 40 + 256) / (6 * 256))


def test_nothing_to_read():
    reset_metrics()
    read = harness.reader("spmm_lane_fill")
    assert read(TRACED) is None                   # no counter: an older program
    _counter("paramspmm_v2_k56_r32_d256", 256, 40)
    assert read({"trace": None}) is None          # untraced run
    # traced, but no SpMM kernel ran on the device (the CPU)
    assert read({"trace": {"op_seconds": {"fusion": 0.1}}}) is None
    assert read(TRACED) == pytest.approx(100.0 * 40 / 256)
