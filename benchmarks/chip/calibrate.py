"""Measurements that set a cell's rate and limits, run on the chip in one
process each (the benchmark's own runs do none of this):

    python3 benchmarks/chip/calibrate.py sweep --workload W --rates 100,150 \\
        --seconds 10 --seed 1
        one set-up, then a window at each offered rate: the tail, the mean
        service time and how far the last request ran past its due time
        (a backlog that grows through the window means the rate is over
        the knee).
    python3 benchmarks/chip/calibrate.py seeds --workload W --seeds 1,2,3 \\
        --seconds 10 [--control] [--rate R]
        one set-up, then per seed: the seed's weights, a window at the
        cell's load, the numbers the check compares and, with --control,
        the same numbers and the cell's verdict for the control (the
        reference one precision step down, in the program's place).
    python3 benchmarks/chip/calibrate.py record --workload W --seconds 0.3 \\
        --out <file>
        a short traced window at the cell's load; its .xplane.pb copied to
        <file> (the tests' recorded trace).
    python3 benchmarks/chip/calibrate.py inspect <trace dir>
        planes, lines and the most frequent op names of a recorded trace.

Each result is one JSON line on standard output.
"""
from __future__ import annotations

import argparse
import collections
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT.parent.parent / "src"))

import harness  # noqa: E402
import model_glue  # noqa: E402
from reference import qwen as R  # noqa: E402

# the control of each version: the reference one precision step below
# what the version states (float32 -> bfloat16; int8 weights -> int4)
SPLIT_CONTROL = {"bf16": ("f32", "bfloat16"), "w4": ("w4", "bfloat16"),
                 "w8": ("w4a8", "float32")}


def emit(obj):
    print(json.dumps(obj), flush=True)


def _setup(args):
    import jax

    import run as entry
    cell = harness.find_cell(args.workload)
    entry.configure_cache(jax)
    entry.require_chips(jax, cell.chips)
    if getattr(args, "rate", None):
        cell.mix["rate_rps"] = float(args.rate)
    compiles = harness.Compiles.install()
    spans = harness.Spans()
    t = time.perf_counter()
    st = cell.driver.setup(cell, args.seed, spans)
    emit({"setup_s": time.perf_counter() - t, "compiles": compiles.total})
    return cell, st, compiles


def sweep(args):
    cell, st, compiles = _setup(args)
    drv = cell.driver
    for rate in [float(r) for r in args.rates.split(",")]:
        cell.mix["rate_rps"] = rate
        st.spans.items.clear()
        t0 = drv.window(st, args.seconds, None, compiles)
        e2e = drv.end_to_end(st)
        last = max(r["done"] for r in st.reqs)
        row = {"rate_rps": rate, **e2e, "requests": len(st.reqs),
               "overrun_s": last - t0 - args.seconds,
               "compiles_in_window": compiles.in_window,
               **drv.summary(st)}
        svc = [r["done"] - r["start"] for r in st.reqs]
        row["service_ms_mean"] = 1e3 * sum(svc) / len(svc)
        lat = [r["latency"] for r in st.reqs]
        row["req_p50_ms"] = 1e3 * harness.quantile(lat, 50)
        row["req_p95_ms"] = 1e3 * harness.quantile(lat, 95)
        emit(row)


def control(st, limits: dict):
    """The control in the program's place: each sampled request's served
    row replaced by the reference's one precision step down, then the
    driver's own numbers and the cell's own comparison. Returns (the
    program's numbers, the control's numbers, whether the control passes,
    per version the median relative error and the widest KL divergence
    of the softmax from the reference's, of each side)."""
    import numpy as np

    def kl(got, ref):
        lp = [np.asarray(x, np.float64) for x in (ref, got)]
        lp = [x - x.max() - np.log(np.exp(x - x.max()).sum()) for x in lp]
        return float(np.sum(np.exp(lp[0]) * (lp[0] - lp[1])))

    drv = st.cell.driver
    prog, ctl, med = {}, {}, {}
    for v, group in drv.sampled(st).items():
        cv, dtype = SPLIT_CONTROL[v]
        ref = drv.reference_rows(st, group, drv.REF_VERSION[v])
        low = drv.reference_rows(st, group, cv, dtype=dtype)
        prog.update(drv.numbers(st, group, v, ref))
        med[v] = {"n": len(group), "program": float(np.median(
            [R.rel_err(st.rows[r["index"]], a) for r, a in zip(group, ref)])),
            "program_kl": max(kl(st.rows[r["index"]], a)
                              for r, a in zip(group, ref))}
        saved = [(st.rows[r["index"]], r["token"]) for r in group]
        for r, row in zip(group, low):
            st.rows[r["index"]], r["token"] = row, int(np.argmax(row))
        ctl.update(drv.numbers(st, group, v, ref))
        med[v]["control"] = float(np.median(
            [R.rel_err(b, a) for b, a in zip(low, ref)]))
        med[v]["control_kl"] = max(kl(b, a) for b, a in zip(low, ref))

        for r, (row, tok) in zip(group, saved):
            st.rows[r["index"]], r["token"] = row, tok
    _, ok = model_glue.check_lines(ctl, limits)
    return prog, ctl, ok, med


def seeds(args):
    cell, st, compiles = _setup(args)
    drv = cell.driver
    for seed in [int(s) for s in args.seeds.split(",")]:
        t = time.perf_counter()
        drv.reseed(st, seed)
        st.spans.items.clear()
        drv.window(st, args.seconds, None, compiles)
        attempted, failed = drv.counts(st)
        row = {"seed": seed, "attempted": attempted, "failed": failed,
               **drv.end_to_end(st), **drv.summary(st),
               "compiles_in_window": compiles.in_window}
        if args.control:
            (row["numbers"], row["control"], row["control_passes"],
             row["median_rel_err"]) = control(st, cell.limits)
        else:
            row["numbers"] = drv.check(st)
        _, row["correct"] = model_glue.check_lines(row["numbers"],
                                                   cell.limits)
        row["seconds"] = time.perf_counter() - t
        emit(row)


def record(args):
    """A short traced window at the cell's load, its trace copied out."""
    import shutil
    cell, st, compiles = _setup(args)
    prof = harness.Profiler(st.spans, harness.trace_dir(cell.name), 0,
                            args.seconds)
    cell.driver.window(st, args.seconds, prof, compiles)
    import trace_reduce
    src = trace_reduce.find_xplane(str(prof.log_dir))
    shutil.copy(src, args.out)
    emit({"trace": args.out, "bytes": Path(args.out).stat().st_size})


def inspect(args):
    from jax.profiler import ProfileData

    import trace_reduce
    data = ProfileData.from_file(trace_reduce.find_xplane(args.dir))
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            names = collections.Counter(e.name for e in line.events)
            lines.append({"line": line.name, "events": sum(names.values()),
                          "top": names.most_common(args.top)})
        emit({"plane": plane.name, "lines": lines})


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    sub = ap.add_subparsers(dest="cmd", required=True)
    a = sub.add_parser("sweep")
    a.add_argument("--workload", required=True)
    a.add_argument("--rates", required=True)
    a.add_argument("--seconds", type=float, default=10)
    a.add_argument("--seed", type=int, default=1)
    b = sub.add_parser("seeds")
    b.add_argument("--workload", required=True)
    b.add_argument("--seeds", required=True)
    b.add_argument("--seconds", type=float, default=10)
    b.add_argument("--control", action="store_true")
    b.add_argument("--rate", type=float)
    d = sub.add_parser("record")
    d.add_argument("--workload", required=True)
    d.add_argument("--seconds", type=float, default=0.3)
    d.add_argument("--seed", type=int, default=1)
    d.add_argument("--out", required=True)
    c = sub.add_parser("inspect")
    c.add_argument("dir")
    c.add_argument("--top", type=int, default=12)
    args = ap.parse_args(argv)
    if args.cmd == "seeds":
        args.seed = int(args.seeds.split(",")[0])
    {"sweep": sweep, "seeds": seeds, "record": record,
     "inspect": inspect}[args.cmd](args)


if __name__ == "__main__":
    main()
