"""What every cell shares: finding a cell's files by name, host spans,
the compile counter, the profiler window, and the record that per-layer
metric readers read.

A cell is one entry of ``workloads`` in ``BENCHMARK.json``. Its files are
found by name, so a later change adds a cell by adding files and an
entry:

    configs/<config>.json        sizes, source, program arch, assumptions
    mixes/<traffic>.json         traffic parameters and the driver's name
    drivers/<driver>.py          setup / window / release / check
    limits/<workload>.json       the limit of each number compared
    layer_metrics/<metric>.py    read(run) -> value or None
    kernel_costs/<kernel>.py     cost(call) -> (operations, bytes, peak)
    peaks.json                   peaks by device_kind
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import os
import re
import shutil
import sys
import time
from pathlib import Path
from typing import Optional

ROOT = Path(__file__).resolve().parent
CHECKOUT = ROOT.parent.parent


def read_json(path: Path):
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    """Import a file of the benchmark by path (names may hold dots)."""
    name = "bench_" + re.sub(r"\W", "_", str(path.with_suffix("")))
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def bench_spec(path: Optional[Path] = None) -> dict:
    return read_json(path or CHECKOUT / "BENCHMARK.json")


@dataclasses.dataclass
class Cell:
    name: str
    workload: dict
    config_entry: dict
    config: dict
    mix: dict
    limits: dict
    end_to_end: list
    per_layer: list

    @property
    def driver(self):
        return load_module(ROOT / "drivers" / f"{self.mix['driver']}.py")

    @property
    def chips(self) -> int:
        return int(self.workload["chips"])


def _reports(metric: dict, cell: str, e2e_names) -> bool:
    """A metric with ``workloads`` is reported in those cells; a
    per-layer one without it wherever the metric it moves is reported."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    return "moves" not in metric or metric["moves"] in e2e_names


def find_cell(name: str, spec: Optional[dict] = None,
              root: Path = ROOT) -> Cell:
    """The cell ``name`` with every file it names, found by name."""
    spec = spec if spec is not None else bench_spec()
    work = {w["name"]: w for w in spec["workloads"]}
    if name not in work:
        raise KeyError(f"unknown workload {name!r}; known: {sorted(work)}")
    w = work[name]
    centry = {c["name"]: c for c in spec["configs"]}[w["config"]]
    config = read_json(root.parent.parent / centry["file"])
    mix = read_json(root / "mixes" / f"{w['traffic']}.json")
    lim_path = root / "limits" / f"{name}.json"
    limits = read_json(lim_path) if lim_path.exists() else {}
    e2e = [m for m in spec["end_to_end"] if _reports(m, name, ())]
    e2e_names = {m["name"] for m in e2e}
    layer = [m for m in spec["per_layer"] if _reports(m, name, e2e_names)]
    return Cell(name, w, centry, config, mix, limits, e2e, layer)


def peaks(device_kind: str) -> dict:
    table = read_json(ROOT / "peaks.json")
    if device_kind not in table:
        raise KeyError(f"no peaks for device_kind {device_kind!r} in "
                       f"peaks.json; known: {sorted(table)}")
    return table[device_kind]


def quantile(values, q: float) -> float:
    import numpy as np
    return float(np.percentile(np.asarray(values, np.float64), q))


# --------------------------------------------------------------------------
# compiles, spans, profiler
# --------------------------------------------------------------------------

class Compiles:
    """Counts programs compiled or loaded from the persistent cache,
    through ``jax.monitoring``: the total, and those inside the measured
    window. Either means a jit traced anew."""

    EVENT = "/jax/core/compile/backend_compile_duration"
    CACHE_HIT = "/jax/compilation_cache/cache_hits"
    _installed = None

    def __init__(self):
        self.total = 0
        self.in_window = 0
        self.window_open = False

    @classmethod
    def install(cls) -> "Compiles":
        if cls._installed is None:
            import jax.monitoring

            counter = cls()

            def count():
                counter.total += 1
                counter.in_window += counter.window_open

            def on_duration(event, duration, **kw):
                if event == cls.EVENT:
                    count()

            def on_event(event, **kw):
                if event == cls.CACHE_HIT:
                    count()

            jax.monitoring.register_event_duration_secs_listener(on_duration)
            jax.monitoring.register_event_listener(on_event)
            cls._installed = counter
        return cls._installed


class Spans:
    """Host-clock spans of the benchmark's calls into the program. While
    the profiler runs, each span is also a ``TraceAnnotation`` named
    ``bench.<name>`` in the trace."""

    def __init__(self):
        self.items = []          # (name, t0, t1, attrs)
        self.tracing = False

    @contextlib.contextmanager
    def span(self, name: str, **attrs):
        ann = None
        if self.tracing:
            import jax
            ann = jax.profiler.TraceAnnotation(f"bench.{name}", **attrs)
            ann.__enter__()
        t0 = time.perf_counter()
        try:
            yield attrs
        finally:
            t1 = time.perf_counter()
            if ann is not None:
                ann.__exit__(None, None, None)
            self.items.append((name, t0, t1, attrs))


class Profiler:
    """The traced part of a ``--trace 1`` window: the profiler starts
    ``start_s`` after the window opens (before it, for 0) and stops
    ``seconds`` later. Drivers call ``poll(elapsed)`` from their loops."""

    def __init__(self, spans: Spans, log_dir: Path, start_s: float,
                 seconds: float):
        self.spans, self.log_dir = spans, log_dir
        self.start_s, self.seconds = float(start_s), float(seconds)
        self.state = "idle"
        self.t_on = self.t_off = None
        self._ann = None

    def _start(self):
        import jax
        shutil.rmtree(self.log_dir, ignore_errors=True)
        self.log_dir.mkdir(parents=True, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(self.log_dir), profiler_options=opts)
        self._ann = jax.profiler.TraceAnnotation("bench.traced")
        self._ann.__enter__()
        self.spans.tracing = True
        self.t_on = time.perf_counter()
        self.state = "on"

    def _stop(self):
        import jax
        self.t_off = time.perf_counter()
        self.spans.tracing = False
        self._ann.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.state = "done"

    def before_window(self):
        if self.start_s <= 0:
            self._start()

    def poll(self, elapsed: float):
        if self.state == "idle" and elapsed >= self.start_s:
            self._start()
        elif self.state == "on" and time.perf_counter() - self.t_on \
                >= self.seconds:
            self._stop()

    def close(self):
        if self.state == "on":
            self._stop()

    def covers(self, t0: float, t1: float) -> bool:
        """Whether a host-clock interval lies inside the traced part."""
        return (self.t_on is not None and self.t_off is not None
                and self.t_on <= t0 and t1 <= self.t_off)


# --------------------------------------------------------------------------
# the record per-layer readers read
# --------------------------------------------------------------------------

@dataclasses.dataclass
class Run:
    """What a window left for the per-layer readers. Host lists and
    counters cover the traced part of the window; ``trace`` is the
    reduced profile of that part (None without a trace)."""
    cell: Cell
    peaks: dict
    host: dict = dataclasses.field(default_factory=dict)
    counters: dict = dataclasses.field(default_factory=dict)
    kernel_calls: dict = dataclasses.field(default_factory=dict)
    model_flops: float = 0.0
    trace: Optional[dict] = None

    def kernel_bound_s(self, kernel: str):
        """(least time the chip could take for the kernel's calls, the
        bound that sets it: "compute" or "memory")."""
        cost = load_module(ROOT / "kernel_costs" / f"{kernel}.py").cost
        t = {"compute": 0.0, "memory": 0.0}
        for call in self.kernel_calls.get(kernel, ()):
            ops, nbytes, peak = cost(call)
            tc = ops / self.peaks[peak]
            tm = nbytes / self.peaks["hbm_bytes_per_s"]
            t["compute" if tc >= tm else "memory"] += max(tc, tm)
        return sum(t.values()), max(t, key=t.get)

    def roofline(self, kernel: str):
        """Share (%) of the kernel's roofline: least time over the
        kernel's device time in the trace."""
        if self.trace is None or not self.kernel_calls.get(kernel):
            return None
        dev = self.trace["kernel_s"].get(kernel, 0.0)
        if dev <= 0:
            return None
        least, _ = self.kernel_bound_s(kernel)
        return 100.0 * least / dev

    def mfu(self):
        """Share (%) of the bf16 peak: model operations of the work that
        ran inside the counted spans, over device busy time there."""
        if self.trace is None or self.model_flops <= 0:
            return None
        busy = self.trace.get("busy_within_s", 0.0)
        if busy <= 0:
            return None
        return 100.0 * self.model_flops / busy / self.peaks["bf16_flops"]

    def mean(self, key: str):
        vals = self.host.get(key) or []
        return sum(vals) / len(vals) if vals else None

    def percentile(self, key: str, q: float):
        vals = self.host.get(key) or []
        return quantile(vals, q) if vals else None


def trace_dir(workload: str) -> Path:
    return CHECKOUT / ".bench_trace" / workload.replace("/", "_")


def cache_dir() -> str:
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        CHECKOUT / ".jax_cache")
