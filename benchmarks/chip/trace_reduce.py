"""Reduction of a profiler trace to the benchmark's device numbers.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes, with
``jax.profiler.ProfileData``, into plain tuples:

- device ops: (chip, name, start_ns, end_ns) of every op on the "XLA Ops"
  line of each ``/device:TPU:n`` plane;
- host spans: (name, start_ns, end_ns, stats) of the benchmark's own
  ``jax.profiler.TraceAnnotation`` spans, whose names start with
  ``bench.``.

The rest works on those tuples alone, so the tests can feed it a
recorded trace or a handmade one:

- busy time: the union of a chip's op intervals inside a window, averaged
  over the chips; idle share is 1 minus busy over the window;
- per-kernel device time, optionally only inside given host spans;
- the breakdown: the device ops that took most time, and the idle time
  of the window summed by what the host was doing (the innermost
  ``bench.`` span around each idle gap).
"""
from __future__ import annotations

import bisect
import collections
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def load(path: str):
    """(device_ops, host_spans) of one trace file."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops, spans = [], []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == OPS_LINE:
                chip = int(m.group(1))
                for e in line.events:
                    ops.append((chip, e.name, float(e.start_ns),
                                float(e.end_ns)))
            elif not m:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.name, float(e.start_ns),
                                      float(e.end_ns), dict(e.stats)))
    return ops, spans


def union(intervals):
    """Disjoint sorted union of (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def busy_ns(ops, lo: float, hi: float, chips=None) -> float:
    """Union of op intervals inside [lo, hi], averaged over chips."""
    by_chip = collections.defaultdict(list)
    for chip, _, s, e in ops:
        by_chip[chip].append((s, e))
    chips = sorted(by_chip) if chips is None else chips
    if not chips:
        return 0.0
    total = sum(sum(e - s for s, e in union(clip(by_chip.get(c, []), lo, hi)))
                for c in chips)
    return total / len(chips)


class Disjoint:
    """Sorted disjoint intervals with fast overlap queries."""

    def __init__(self, intervals):
        u = union(intervals)
        self.starts = [s for s, _ in u]
        self.ends = [e for _, e in u]

    def overlap(self, s: float, e: float) -> float:
        i = max(bisect.bisect_right(self.starts, s) - 1, 0)
        total = 0.0
        while i < len(self.starts) and self.starts[i] < e:
            total += max(0.0, min(e, self.ends[i]) - max(s, self.starts[i]))
            i += 1
        return total


_OP_NAME = re.compile(r"%?([^\s=]+)")


def op_kind(name: str) -> str:
    """An op's kind: its HLO instruction name without the instance
    number. "%flash_attention.3 = f32[...] custom-call(...)" and
    "flash_attention.3" both give "flash_attention"."""
    m = _OP_NAME.match(name)
    return re.sub(r"\.\d+$", "", m.group(1) if m else name)


def kernel_ns(ops, kernel: str, within=None) -> float:
    """Device time of the ops of kind ``kernel``, counted only inside
    ``within`` (a list of (start, end)) where given."""
    spans = Disjoint(within) if within is not None else None
    total = 0.0
    for _, name, s, e in ops:
        if op_kind(name) == kernel:
            total += e - s if spans is None else spans.overlap(s, e)
    return total


def self_times(ops):
    """[(chip, name, start, end, self ns)]: an op's time minus the time
    of the ops nested in it on its chip's line (a while loop holds the
    ops of its body)."""
    out = []
    by_chip = collections.defaultdict(list)
    for op in ops:
        by_chip[op[0]].append(op)
    for chip, lst in by_chip.items():
        lst.sort(key=lambda o: (o[2], -o[3]))
        stack = []                      # [op, self] of open ancestors
        for op in lst:
            while stack and stack[-1][0][3] <= op[2]:
                out.append((*stack[-1][0], stack.pop()[1]))
            if stack:
                stack[-1][1] -= min(op[3], stack[-1][0][3]) - op[2]
            stack.append([op, op[3] - op[2]])
        out.extend((*o, selft) for o, selft in stack)
    return out


def top_ops(ops, lo: float, hi: float, n: int = 10):
    """[[op kind, seconds]] of the n op kinds with most self time on the
    device among the ops that start in [lo, hi], averaged over chips."""
    chips = {c for c, *_ in ops} or {0}
    acc = collections.Counter()
    for _, name, s, e, own in self_times(ops):
        if lo <= s < hi:
            acc[op_kind(name)] += own
    return [[k, v / len(chips) / 1e9] for k, v in acc.most_common(n)]


def idle_by_host(ops, spans, lo: float, hi: float, n: int = 10):
    """[[host activity, seconds]]: the window's idle time (no op on chip
    0 or the first chip) summed by the innermost host span around each
    idle stretch, most first. Idle time under no span is "other"."""
    chips = sorted({c for c, *_ in ops})
    first = chips[0] if chips else 0
    busy = union(clip([(s, e) for c, _, s, e in ops if c == first], lo, hi))
    gaps, cur = [], lo
    for s, e in busy:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if cur < hi:
        gaps.append((cur, hi))
    gaps = Disjoint(gaps)
    spans = sorted((sp for sp in spans if sp[0] != "bench.traced"),
                   key=lambda sp: sp[1])
    starts = [sp[1] for sp in spans]
    # elementary intervals between every span and gap boundary
    marks = sorted({lo, hi, *[x for sp in spans for x in sp[1:3]
                              if lo < x < hi]})
    acc = collections.Counter()
    for a, b in zip(marks, marks[1:]):
        idle = gaps.overlap(a, b)
        if idle <= 0:
            continue
        mid = 0.5 * (a + b)
        j = bisect.bisect_right(starts, mid)
        # innermost span around mid, among the few that start last
        around = [sp for sp in spans[max(j - 8, 0):j] if mid < sp[2]]
        label = (min(around, key=lambda sp: sp[2] - sp[1])[0][
            len(SPAN_PREFIX):] if around else "other")
        acc[label] += idle
    return [[k, v / 1e9] for k, v in acc.most_common(n)]


def reduce(ops, spans, lo: float, hi: float, kernels=(), within=None):
    """The trace's numbers for the window [lo, hi] (ns)."""
    chips = sorted({c for c, *_ in ops})
    window_s = (hi - lo) / 1e9
    busy_s = busy_ns(ops, lo, hi, chips) / 1e9
    return {
        "window_s": window_s,
        "busy_s": busy_s,
        "idle_share": 1.0 - busy_s / window_s if window_s > 0 else None,
        "kernel_s": {k: kernel_ns(ops, k, within) / 1e9 for k in kernels},
        "busy_within_s": (busy_ns(ops, lo, hi, chips) if within is None
                          else _busy_within(ops, chips, within)) / 1e9,
        "breakdown": {"device_ops": top_ops(ops, lo, hi),
                      "idle_gaps": idle_by_host(ops, spans, lo, hi)},
    }


def _busy_within(ops, chips, within) -> float:
    spans = Disjoint(within)
    total = 0.0
    for c in chips:
        u = union([(s, e) for cc, _, s, e in ops if cc == c])
        total += sum(spans.overlap(s, e) for s, e in u)
    return total / max(len(chips), 1)
