"""trace_reduce on a handmade trace whose answers are worked out by
hand, and on a trace recorded on the chip."""
import os
import sys
from pathlib import Path

# the benchmark's modules and the program, CPU only (no conftest here:
# its module name would collide with the repository's tests/conftest.py)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
for _p in (Path(__file__).resolve().parents[1],
           Path(__file__).resolve().parents[3] / "src"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))


import pytest

import trace_reduce as T

MS = 1e6  # ns

# chip 0: ops at [0,2] and [1,3] overlap (union 3 ms), then [5,6]
# chip 1: [0,1]
OPS = [(0, "fusion.1", 0 * MS, 2 * MS), (0, "flash_attention.3", 1 * MS,
                                         3 * MS),
       (0, "flash_attention", 5 * MS, 6 * MS), (1, "copy.2", 0, 1 * MS)]
SPANS = [("bench.traced", 0.0, 10 * MS, {}),
         ("bench.infer", 0.0, 4 * MS, {"rid": 1}),
         ("bench.wait", 4 * MS, 5 * MS, {}),
         ("bench.infer", 5 * MS, 7 * MS, {"rid": 2})]


def test_busy_is_the_union_averaged_over_chips():
    assert T.busy_ns(OPS, 0, 10 * MS) == pytest.approx((4 + 1) / 2 * MS)
    assert T.busy_ns(OPS, 0, 10 * MS, chips=[0]) == pytest.approx(4 * MS)
    # clipped to the window
    assert T.busy_ns(OPS, 2 * MS, 5.5 * MS, chips=[0]) == pytest.approx(
        1.5 * MS)


def test_kernel_time_by_name_and_within_spans():
    assert T.kernel_ns(OPS, "flash_attention") == pytest.approx(3 * MS)
    assert T.kernel_ns(OPS, "flash_attention",
                       within=[(0, 2.5 * MS)]) == pytest.approx(1.5 * MS)
    assert T.kernel_ns(OPS, "flash") == 0.0


def test_reduce_and_idle_labels():
    red = T.reduce(OPS, SPANS, 0.0, 10 * MS, kernels=("flash_attention",),
                   within=[(0, 4 * MS), (5 * MS, 7 * MS)])
    assert red["window_s"] == pytest.approx(0.010)
    assert red["busy_s"] == pytest.approx(0.0025)
    assert red["idle_share"] == pytest.approx(0.75)
    assert red["kernel_s"]["flash_attention"] == pytest.approx(0.003)
    # chip 0 idle: [3,4] in infer, [4,5] in wait, [6,7] in infer,
    # [7,10] under no span
    gaps = dict(red["breakdown"]["idle_gaps"])
    assert gaps == pytest.approx({"infer": 0.002, "wait": 0.001,
                                  "other": 0.003})
    ops = dict(red["breakdown"]["device_ops"])
    assert ops["flash_attention"] == pytest.approx(0.003 / 2)


RECORDED = Path(__file__).parent / "data" / "split_small.xplane.pb"


def test_recorded_chip_trace():
    """A 0.1-s traced window of qwen2-0.5b.split-poisson at 30 req/s on
    one v5e chip (calibrate.py record): three requests, 4930 ops."""
    ops, spans = T.load(str(RECORDED))
    assert len(ops) == 4930 and {c for c, *_ in ops} == {0}
    traced = [s for s in spans if s[0] == "bench.traced"]
    assert len(traced) == 1
    lo, hi = traced[0][1], traced[0][2]
    infer = [(s[1], s[2]) for s in spans if s[0] == "bench.infer"
             and lo <= s[1] and s[2] <= hi]
    assert len(infer) == 3
    red = T.reduce(ops, spans, lo, hi,
                   kernels=("flash_attention", "quant_matmul"),
                   within=infer)
    assert red["window_s"] == pytest.approx(0.11474514)
    assert red["busy_s"] == pytest.approx(0.059762143)
    assert red["idle_share"] == pytest.approx(0.47917495, rel=1e-6)
    assert red["kernel_s"]["flash_attention"] == pytest.approx(0.024417201)
    assert red["kernel_s"]["quant_matmul"] == pytest.approx(0.00838453)
    # self times add up to the busy time: nested loop bodies count once
    assert sum(o[4] for o in T.self_times(ops)) == pytest.approx(
        T.busy_ns(ops, -1e18, 1e18))
    ops_top = dict(red["breakdown"]["device_ops"])
    assert next(iter(ops_top)) == "flash_attention"
    gaps = dict(red["breakdown"]["idle_gaps"])
    assert gaps["wait"] == pytest.approx(0.045883216)
    assert sum(gaps.values()) == pytest.approx(
        red["window_s"] - red["busy_s"], rel=1e-6)
