"""The reduction from a profiler trace to numbers: on a synthetic trace
with an inserted gap, and on a small trace cut from this PR's own chip run
(``benchmarks/fixtures``), read back to hand-checked values."""

import collections
import gzip
import json
import os

import pytest

from benchmarks import harness, trace_reduce

E = collections.namedtuple("E", "name start_ns duration_ns")
L = collections.namedtuple("L", "name events")
P = collections.namedtuple("P", "name lines")
MS = 1_000_000


def synthetic(gap_ms=0):
    """Two runs of ``jit_step_fn`` of 10 ms each (a 6 ms convolution, a
    3 ms fusion, a 1 ms kernel), ``gap_ms`` apart, with the host inside
    ``bench/loader_next`` for the whole of the gap."""
    ops, modules = [], []
    for k in range(2):
        t = k * (10 + gap_ms) * MS
        ops += [E("convolution.1", t, 6 * MS), E("fusion.7", t + 6 * MS, 3 * MS),
                E("my_kernel", t + 9 * MS, 1 * MS)]
        modules.append(E("jit_step_fn(123)", t, 10 * MS))
    host = [E("bench/loader_next", 10 * MS, gap_ms * MS),
            E("other_host_work", 0, 5 * MS)]
    return [P("/device:TPU:0", [L("XLA Ops", ops), L("XLA Modules", modules),
                                L("Steps", [E("1", 0, 99 * MS)])]),
            P("/host:CPU", [L("main", host)]),
            P("/host:metadata", [])]


def test_busy_window_and_idle_without_a_gap():
    red = trace_reduce.reduce_planes(synthetic(0))
    assert red.window_s == pytest.approx(0.020)
    assert red.busy_s == pytest.approx(0.020)
    assert red.idle_gaps() == []


def test_an_inserted_gap_shows_as_idle_and_is_charged_to_the_host_span():
    red = trace_reduce.reduce_planes(synthetic(5))
    assert red.window_s == pytest.approx(0.025)
    assert red.busy_s == pytest.approx(0.020)
    assert red.idle_gaps() == [["bench/loader_next", pytest.approx(0.005)]]
    assert red.program("jit_step_fn") == (pytest.approx(0.020), 2)
    assert red.program("jit_step") == (0.0, 0)
    assert red.pattern_seconds(r"^my_kernel$") == (pytest.approx(0.002), 2)
    assert red.top_ops(2) == [["convolution.1", pytest.approx(0.012)],
                              ["fusion.7", pytest.approx(0.006)]]


def test_union_and_gaps_of_overlapping_intervals():
    spans = [(0.0, 2.0), (1.0, 3.0), (5.0, 6.0), (5.5, 5.8)]
    assert trace_reduce.union_seconds(spans) == pytest.approx(4.0)
    assert trace_reduce.gaps(spans) == [(3.0, 5.0)]
    assert trace_reduce.union_seconds([]) == 0.0


def test_readers_on_the_synthetic_trace():
    from benchmarks.readers import (device_idle_share, kernel_roofline,
                                    program_mfu)

    red = trace_reduce.reduce_planes(synthetic(5))
    peaks = harness.load_peaks("TPU v5 lite")
    ctx = {"trace": red, "peaks": peaks,
           "window": {"batch": 64, "resolution": 300, "num_classes": 21}}
    assert device_idle_share.read(ctx, {}) == pytest.approx(20.0)
    mfu = program_mfu.read(ctx, {"program": "jit_step_fn",
                                 "flops": "ssd_train_step"})
    # 2 steps x 3 x 64 x 62.7 GFLOP in 20 ms of 197 TFLOP/s
    assert mfu == pytest.approx(100 * 2 * 3 * 64 * 62.747e9
                                / (0.020 * 197e12), rel=1e-3)
    roof = kernel_roofline.read(ctx, {"pattern": "^my_kernel$",
                                      "cost": "detection_output"})
    assert 0 < roof < 100
    assert kernel_roofline.read(ctx, {"pattern": "^absent$",
                                      "cost": "detection_output"}) is None


def test_a_cut_of_this_prs_own_chip_trace_reads_back_to_hand_checked_values():
    """0.45 s of `ssd300-train-b64`'s traced run (TPU v5 lite, PR 25):
    4,832 operations, one whole run of the step program."""
    path = os.path.join(harness.HERE, "fixtures",
                        "ssd300-train-b64.trace_cut.json.gz")
    planes = trace_reduce.load_cut(path)
    assert [p.name for p in planes] == ["/device:TPU:0", "/host:CPU"]
    red = trace_reduce.reduce_planes(planes)
    ops = red.devices[0].ops
    assert len(ops) == 4832
    # the core runs one operation at a time: busy is the sum of durations
    assert red.busy_s == pytest.approx(sum(d for _, _, d in ops), rel=1e-9)
    assert red.busy_s == pytest.approx(0.370986423, rel=1e-6)
    assert red.window_s == pytest.approx(0.448841788, rel=1e-6)
    seconds, runs = red.program("jit_step_fn")
    assert runs == 1 and seconds == pytest.approx(0.133150377, rel=1e-6)
    assert red.pattern_seconds(r"^%fusion\.617 = ") == (
        pytest.approx(0.019843203, rel=1e-6), 2)
    assert red.top_ops(1) == [["fusion.2", pytest.approx(0.022767661)]]
    # every idle stretch but 14 microseconds lies inside the loader's next
    gaps = dict(red.idle_gaps())
    assert gaps["bench/loader_next"] == pytest.approx(0.077841721, rel=1e-6)
    assert sum(gaps.values()) == pytest.approx(red.window_s - red.busy_s,
                                               rel=1e-6)
    # the whole step's share of peak, from this one run: 3 x 64 x 62.7 GFLOP
    from benchmarks.readers import program_mfu

    mfu = program_mfu.read(
        {"trace": red, "peaks": harness.load_peaks("TPU v5 lite"),
         "window": {"batch": 64, "resolution": 300, "num_classes": 21}},
        {"program": "jit_step_fn", "flops": "ssd_train_step"})
    assert mfu == pytest.approx(45.93, abs=0.02)
