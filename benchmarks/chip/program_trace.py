"""The program's own spans and programs on the device trace's clock, for
the split path's per-layer readers.

``load`` reads one ``.xplane.pb`` in one pass into plain tuples, like
``trace_reduce.load``:

- modules: (chip, name, start_ns, end_ns) of every program on the
  "XLA Modules" line of each ``/device:TPU:n`` plane, such as
  ``jit_split_head(<hash>)``;
- ops: (chip, start_ns, end_ns) of every op on the "XLA Ops" line, whose
  union is the chip's busy time, as ``trace_reduce`` counts it;
- host spans: (name, start_ns, end_ns, stats) of the program's ``repro.``
  annotations (``obs.span`` mirrored into the profiler) and the
  benchmark's ``bench.`` spans.

A request is traced when its ``bench.infer`` span (with ``rid``) lies
inside ``bench.traced``; its program spans are the ``repro.`` spans that
its ``bench.infer`` contains, and its modules those of the first chip
that start inside it (the caller's fetch serializes requests, so each
module belongs to one). ``of(run)`` loads a run's trace once for all its
readers. Where the program has no such span or module, as before the
split path was spanned, a reader finds nothing and returns None.
"""
from __future__ import annotations

import bisect
import dataclasses

import harness
import trace_reduce

MODULES_LINE = "XLA Modules"
PREFIXES = ("repro.", trace_reduce.SPAN_PREFIX)


@dataclasses.dataclass
class Request:
    rid: int
    start: float                 # its bench.infer span, ns
    end: float
    spans: list                  # (name, start, end, stats) of repro. spans
    modules: list                # (name, start, end) on the first chip

    def span(self, name: str):
        """The request's first program span named ``name``, or None."""
        return next((s for s in self.spans if s[0] == name), None)


class ProgramTrace:
    """A trace's traced requests, with the first chip's busy time."""

    def __init__(self, modules, ops, spans):
        chips = sorted({c for c, *_ in ops} | {c for c, *_ in modules})
        self.has_device = bool(chips)
        first = chips[0] if chips else 0
        self.busy = trace_reduce.Disjoint(
            [(s, e) for c, s, e in ops if c == first])
        traced = [s for s in spans if s[0] == "bench.traced"]
        lo, hi = ((traced[0][1], traced[0][2]) if traced
                  else (float("-inf"), float("inf")))
        program = sorted((s for s in spans if s[0].startswith("repro.")),
                         key=lambda s: s[1])
        p_starts = [s[1] for s in program]
        mods = sorted(((n, s, e) for c, n, s, e in modules if c == first),
                      key=lambda m: m[1])
        m_starts = [m[1] for m in mods]
        self.requests = []
        for name, s, e, stats in sorted(spans, key=lambda sp: sp[1]):
            if name != "bench.infer" or "rid" not in stats \
                    or not (lo <= s and e <= hi):
                continue
            i, j = (bisect.bisect_left(p_starts, s),
                    bisect.bisect_left(p_starts, e))
            k, m = (bisect.bisect_left(m_starts, s),
                    bisect.bisect_left(m_starts, e))
            self.requests.append(Request(
                int(stats["rid"]), s, e,
                [sp for sp in program[i:j] if sp[2] <= e], mods[k:m]))

    def idle_ns(self, lo: float, hi: float) -> float:
        """Time in [lo, hi] with no op on the first chip."""
        return max(hi - lo, 0.0) - self.busy.overlap(lo, hi)


def load(path: str) -> ProgramTrace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    modules, ops, spans = [], [], []
    for plane in data.planes:
        m = trace_reduce.DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == MODULES_LINE:
                chip = int(m.group(1))
                modules += [(chip, e.name, float(e.start_ns),
                             float(e.end_ns)) for e in line.events]
            elif m and line.name == trace_reduce.OPS_LINE:
                chip = int(m.group(1))
                ops += [(chip, float(e.start_ns), float(e.end_ns))
                        for e in line.events]
            elif not m:
                spans += [(e.name, float(e.start_ns), float(e.end_ns),
                           dict(e.stats)) for e in line.events
                          if e.name.startswith(PREFIXES)]
    return ProgramTrace(modules, ops, spans)


def of(run) -> ProgramTrace:
    """The run's program trace, loaded once for all readers."""
    pt = getattr(run, "program_trace", None)
    if pt is None:
        pt = run.program_trace = load(trace_reduce.find_xplane(
            str(harness.trace_dir(run.cell.name))))
    return pt


def _mean_ms(values):
    return sum(values) / len(values) / 1e6 if values else None


def module_ms(run, program: str):
    """Mean over traced requests of the device time of the modules of the
    program ``program`` (``jit_<function name>``) inside each request."""
    pt = of(run)
    if not pt.has_device:
        return None
    per = [[e - s for n, s, e in r.modules if n.split("(", 1)[0] == program]
           for r in pt.requests]
    return _mean_ms([sum(t) for t in per]) if any(per) else None


def span_ms(run, name: str, version: str):
    """Mean host duration of the program span ``repro.<name>`` over the
    traced requests served at ``version``."""
    durs = []
    for r in of(run).requests:
        infer, sp = r.span("repro.split.infer"), r.span(f"repro.{name}")
        if infer and sp and infer[3].get("version") == version:
            durs.append(sp[2] - sp[1])
    return _mean_ms(durs)


def idle_ms(run, part: str):
    """Mean per traced request of the first chip's idle time inside the
    request's ``repro.split.infer`` (``part="dispatch"``), or inside its
    ``bench.infer`` after ``repro.split.infer`` ends (``part="fetch"``:
    the result handoff and the caller's fetch)."""
    pt = of(run)
    if not pt.has_device:
        return None
    idle = []
    for r in pt.requests:
        sp = r.span("repro.split.infer")
        if sp:
            idle.append(pt.idle_ns(sp[1], sp[2]) if part == "dispatch"
                        else pt.idle_ns(sp[2], r.end))
    return _mean_ms(idle)
