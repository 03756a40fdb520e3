"""One run of one benchmark cell on the accelerator.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

One process: it finds the cell's files by name (see ``harness.py``),
loads and warms up (``setup_s``), measures for ``--seconds``, reads the
device's peak memory, frees the program's state, checks what the timed
path produced against the plain reference, and prints one JSON line as
the last line of its standard output. ``--trace 0`` reports the cell's
end-to-end metrics, ``--trace 1`` its per-layer metrics from a profiled
part of the window. Without a TPU, or with fewer chips than the cell
asks for, it exits nonzero before any work.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT.parent.parent / "src"))

import harness  # noqa: E402
from model_glue import check_lines  # noqa: E402


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def require_chips(jax, chips: int):
    """The devices of the run; exits when they are not TPUs enough."""
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"run.py: needs a TPU; JAX found {devs[0].platform!r} "
                 "devices only")
    if len(devs) < chips:
        sys.exit(f"run.py: the cell asks for {chips} chips, JAX sees "
                 f"{len(devs)}")
    return devs[:chips]


def configure_cache(jax):
    jax.config.update("jax_compilation_cache_dir", harness.cache_dir())
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def peak_memory(devs) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs)


def per_layer(cell, run: harness.Run) -> dict:
    out = {}
    for m in cell.per_layer:
        reader = harness.load_module(
            harness.ROOT / "layer_metrics" / f"{m['name']}.py")
        value = reader.read(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def measure(cell, seed: int, seconds: float, trace: bool, devs, peaks,
            reduced: bool = False, t_start: float = None):
    """Set up, run the window, check. Returns the result's fields and the
    lines of the comparison. ``reduced`` (CPU tests only) runs the
    program's small variant of the configuration."""
    t_start = T_START if t_start is None else t_start
    compiles = harness.Compiles.install()
    spans = harness.Spans()
    drv = cell.driver
    st = drv.setup(cell, seed, spans, reduced=reduced)
    prof = None
    if trace:
        mix = cell.mix
        prof = harness.Profiler(spans, harness.trace_dir(cell.name),
                                mix.get("trace_start_s", 0),
                                mix["trace_seconds"])
    t0 = drv.window(st, seconds, prof, compiles)
    setup_s = t0 - t_start
    attempted, failed = drv.counts(st)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak_memory(devs)}
    if trace:
        run = harness.Run(cell=cell, peaks=peaks)
        drv.run_record(st, prof, run)
        metrics = per_layer(cell, run)
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        extra = {"breakdown": run.trace["breakdown"]}
    else:
        e2e = drv.end_to_end(st)
        e2e["setup_s"] = setup_s
        metrics = {m["name"]: {"value": float(e2e[m["name"]]),
                               "unit": m["unit"]}
                   for m in cell.end_to_end if m["name"] in e2e}
        extra = {}
    summary = drv.summary(st)
    drv.release(st)
    numbers = drv.check(st)
    lines, ok = check_lines(numbers, cell.limits)
    correct = bool(ok and failed == 0 and lines)
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device, **extra,
              "checks": {n: {"value": v, "limit": lim}
                         for n, v, lim in lines}}
    return result, lines, summary


def main(argv=None):
    args = parse(argv)
    cell = harness.find_cell(args.workload)
    import jax

    configure_cache(jax)
    devs = require_chips(jax, cell.chips)
    peaks = harness.peaks(devs[0].device_kind)
    result, lines, summary = measure(cell, args.seed, args.seconds,
                                     bool(args.trace), devs, peaks)
    print(f"summary {json.dumps(summary)}", file=sys.stderr)
    for name, value, limit in lines:
        print(f"check {name} {value!r} limit {limit!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
