"""The split path's program-span readers (program_trace.py and its five
layer metrics) on a handmade trace whose answers are worked out by hand,
on a trace recorded on the chip, and in a traced CPU rehearsal."""
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

import harness
import program_trace as P
import trace_reduce as T

MS = 1e6  # ns
READERS = ("head_device_ms.split", "tail_device_ms.split", "link_ms.split",
           "dispatch_idle_ms.split", "fetch_idle_ms.split")
DEVICE_READERS = tuple(r for r in READERS if r != "link_ms.split")

# Request 1 (w8): bench.infer [1, 8] ms, repro.split.infer [1.2, 3] with
# head [1.2, 1.6], link [1.6, 2.4], tail [2.4, 3]; on the chip the head
# program runs [1.5, 4.5], a link module [4.5, 4.6], the tail program
# [4.6, 7], the caller's fetch [7.2, 7.3].
# Request 2 (bf16): bench.infer [10, 14], repro.split.infer [10.1, 10.5]
# with head [10.1, 10.2], link [10.2, 10.25], tail [10.25, 10.5]; head
# program [10.3, 11.3], tail program [11.3, 13].
# Request 3 ends after bench.traced [0, 20] does, so it is not traced;
# the decide program [9, 9.5] lies in no request.
SPANS = [
    ("bench.traced", 0.0, 20 * MS, {}),
    ("bench.infer", 1 * MS, 8 * MS, {"rid": 1}),
    ("repro.split.infer", 1.2 * MS, 3 * MS,
     {"version": "w8", "cut": "('main', 5)", "S": 256}),
    ("repro.split.head", 1.2 * MS, 1.6 * MS, {}),
    ("repro.split.link", 1.6 * MS, 2.4 * MS, {}),
    ("repro.split.tail", 2.4 * MS, 3 * MS, {}),
    ("bench.decide", 9 * MS, 9.6 * MS, {"slot": 1}),
    ("bench.infer", 10 * MS, 14 * MS, {"rid": 2}),
    ("repro.split.infer", 10.1 * MS, 10.5 * MS,
     {"version": "bf16", "cut": "('main', 3)", "S": 1024}),
    ("repro.split.head", 10.1 * MS, 10.2 * MS, {}),
    ("repro.split.link", 10.2 * MS, 10.25 * MS, {}),
    ("repro.split.tail", 10.25 * MS, 10.5 * MS, {}),
    ("bench.infer", 19 * MS, 22 * MS, {"rid": 3}),
    ("repro.split.infer", 19 * MS, 19.5 * MS, {"version": "w8"}),
    ("repro.split.link", 19.1 * MS, 19.4 * MS, {}),
]
MODULES = [(0, "jit_split_head(11)", 1.5 * MS, 4.5 * MS),
           (0, "jit_abs(12)", 4.5 * MS, 4.6 * MS),
           (0, "jit_split_tail(13)", 4.6 * MS, 7 * MS),
           (0, "jit_squeeze(14)", 7.2 * MS, 7.3 * MS),
           (0, "jit__act(15)", 9 * MS, 9.5 * MS),
           (0, "jit_split_head(11)", 10.3 * MS, 11.3 * MS),
           (0, "jit_split_tail(16)", 11.3 * MS, 13 * MS),
           (0, "jit_split_head(17)", 19.5 * MS, 21 * MS)]
OPS = [(c, s, e) for c, _, s, e in MODULES]


def run_of(trace: P.ProgramTrace) -> harness.Run:
    run = harness.Run(cell=None, peaks={})
    run.program_trace = trace
    return run


def read_all(run) -> dict:
    return {name: harness.load_module(
        harness.ROOT / "layer_metrics" / f"{name}.py").read(run)
        for name in READERS}


def test_readers_on_a_handmade_trace():
    pt = P.ProgramTrace(MODULES, OPS, SPANS)
    assert [r.rid for r in pt.requests] == [1, 2]
    got = read_all(run_of(pt))
    assert got == pytest.approx({
        # head programs: 3.0 and 1.0 ms; tail programs: 2.4 and 1.7 ms
        "head_device_ms.split": (3.0 + 1.0) / 2,
        "tail_device_ms.split": (2.4 + 1.7) / 2,
        # the one traced w8 request's link span: 2.4 - 1.6 ms
        "link_ms.split": 0.8,
        # idle inside repro.split.infer: [1.2, 1.5] and [10.1, 10.3]
        "dispatch_idle_ms.split": (0.3 + 0.2) / 2,
        # idle after it until bench.infer ends: [7, 7.2] and [7.3, 8];
        # [13, 14]
        "fetch_idle_ms.split": (0.9 + 1.0) / 2})


def test_readers_find_nothing_in_an_unspanned_program():
    """A program without the split spans and named programs (the parent
    of this change): every reader returns None and none raises."""
    spans = [s for s in SPANS if s[0].startswith("bench.")]
    modules = [(c, "jit__lambda(9)", s, e) for c, _, s, e in MODULES]
    assert read_all(run_of(P.ProgramTrace(modules, OPS, spans))) == {
        name: None for name in READERS}


def test_without_a_device_only_the_host_reader_reads():
    got = read_all(run_of(P.ProgramTrace([], [], SPANS)))
    assert got == pytest.approx({**{n: None for n in DEVICE_READERS},
                                 "link_ms.split": 0.8})


RECORDED = Path(__file__).parent / "data" / "split_spans.xplane.pb"
# its readings: a bf16 request of 1024 tokens cut at 14, then a w8
# request of 256 cut at 5, whose link dispatch outlasts its head program
PINNED = {"head_device_ms.split": 9.928183,
          "tail_device_ms.split": 12.283087, "link_ms.split": 3.211149,
          "dispatch_idle_ms.split": 0.8108815,
          "fetch_idle_ms.split": 1.412023}


def test_recorded_chip_trace():
    """A 0.086-s traced window of qwen2-0.5b.split-poisson at 35 req/s
    on one v5e chip (calibrate.py record) with the split path spanned and
    its programs named: two requests traced."""
    pt = P.load(str(RECORDED))
    assert pt.has_device and [r.rid for r in pt.requests] == [0, 1]
    got = read_all(run_of(pt))
    assert got == pytest.approx(PINNED, rel=1e-6)
    for r in pt.requests:
        assert [s[0] for s in r.spans] == [
            "repro.split.infer", "repro.split.head", "repro.split.link",
            "repro.split.tail"]
        names = [n.split("(")[0] for n, _, _ in r.modules]
        assert names.count("jit_split_head") == 1
        assert names.count("jit_split_tail") == 1
        assert names.index("jit_split_head") < names.index("jit_split_tail")
        assert not any(n == "jit__lambda" for n in names)
    # head and tail are nearly all of the device's busy time inside the
    # requests' bench.infer spans
    n = len(pt.requests)
    busy_ms = sum(pt.busy.overlap(r.start, r.end) for r in pt.requests) \
        / n / 1e6
    device_ms = got["head_device_ms.split"] + got["tail_device_ms.split"]
    assert 0.95 * busy_ms <= device_ms <= busy_ms
    # the idle time inside the requests splits into dispatch and fetch:
    # together they are the breakdown's "infer" idle, less the moment
    # before each request's program span opens
    ops, spans = T.load(str(RECORDED))
    traced = next(s for s in spans if s[0] == "bench.traced")
    gaps = dict(T.idle_by_host(ops, spans, traced[1], traced[2]))
    idle_s = n * (got["dispatch_idle_ms.split"]
                  + got["fetch_idle_ms.split"]) / 1e3
    assert idle_s == pytest.approx(gaps["infer"], rel=0.10)
    assert idle_s <= gaps["infer"]


def test_traced_rehearsal_reads_only_the_host_span(monkeypatch, tmp_path):
    """A traced CPU rehearsal: the program's spans reach the profiler's
    trace, so link_ms.split reads; the CPU trace has no TPU plane, so no
    device-trace reader does."""
    import test_bench_chip_rehearsal as rehearsal

    monkeypatch.setenv("REPRO_USE_PALLAS", "interpret")
    monkeypatch.setattr(harness, "trace_dir",
                        lambda workload: tmp_path / workload)
    result, _, _ = rehearsal.measure(trace=True)
    m = result["metrics"]
    assert m["link_ms.split"]["value"] > 0
    assert m["link_ms.split"]["unit"] == "ms"
    assert not set(DEVICE_READERS) & set(m)
