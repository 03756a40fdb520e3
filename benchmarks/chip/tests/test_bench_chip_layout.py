"""The harness finds a cell's files by name, and a new cell is new files
plus one entry: no file the benchmark already has changes."""
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

import hashlib
import json
import shutil
import subprocess

import pytest

import harness

REPO = harness.CHECKOUT


def digest(root: Path) -> dict:
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes())
            .hexdigest() for p in sorted(root.rglob("*")) if p.is_file()
            and "__pycache__" not in p.parts}


def test_every_cell_resolves_by_name():
    spec = harness.bench_spec()
    for w in spec["workloads"]:
        cell = harness.find_cell(w["name"], spec)
        assert (harness.ROOT / "drivers" / f"{cell.mix['driver']}.py").exists()
        assert cell.limits, f"{w['name']} has no limits file"
        assert any(m["name"] == "setup_s" for m in cell.end_to_end)
        assert len(cell.end_to_end) >= 2 and cell.per_layer
        for m in cell.per_layer:
            assert (harness.ROOT / "layer_metrics"
                    / f"{m['name']}.py").exists(), m["name"]
            assert m["moves"] in {e["name"] for e in cell.end_to_end}


def test_new_cell_is_new_files_and_one_entry(tmp_path):
    repo = tmp_path / "repo"
    bench = repo / "benchmarks" / "chip"
    shutil.copytree(harness.ROOT, bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", repo / "BENCHMARK.json")
    before = digest(bench)

    spec = json.loads((repo / "BENCHMARK.json").read_text())
    base = spec["workloads"][0]
    old = harness.find_cell(base["name"], spec, root=bench)
    # a new traffic mix, a new per-layer metric, a new kernel's costs and
    # the new cell's limits: files only
    mix = dict(old.mix, rate_rps=1.0)
    (bench / "mixes" / "burst-test.json").write_text(json.dumps(mix))
    (bench / "limits" / "new.cell.json").write_text(json.dumps(old.limits))
    (bench / "layer_metrics" / "new_metric.test.py").write_text(
        "def read(run):\n    return 42.0\n")
    (bench / "kernel_costs" / "new_kernel.py").write_text(
        "def cost(call):\n    return 1, 1, 'bf16_flops'\n")
    spec["workloads"].append(dict(base, name="new.cell",
                                  traffic="burst-test"))
    e2e = next(m["name"] for m in old.end_to_end if m["name"] != "setup_s")
    spec["per_layer"].append({"name": "new_metric.test", "unit": "x",
                              "better": "higher", "source": "host_clock",
                              "layer": "test", "moves": e2e,
                              "workloads": ["new.cell"]})
    for m in spec["end_to_end"]:
        if "workloads" in m and e2e == m["name"]:
            m["workloads"].append("new.cell")

    cell = harness.find_cell("new.cell", spec, root=bench)
    assert cell.mix["rate_rps"] == 1.0
    assert cell.limits == old.limits
    assert "new_metric.test" in [m["name"] for m in cell.per_layer]
    reader = harness.load_module(bench / "layer_metrics"
                                 / "new_metric.test.py")
    assert reader.read(None) == 42.0
    after = digest(bench)
    assert {k: v for k, v in after.items() if k in before} == before


def test_every_run_replays_the_mix_trace():
    """The mix's work_seed alone fixes the arrival times and each field's
    values, in exact counts and a shuffled order."""
    import numpy as np

    import traffic
    mix = harness.find_cell("qwen2-0.5b.split-poisson").mix
    a = traffic.open_loop(mix, 10.0)
    assert a == traffic.open_loop(dict(mix), 10.0)
    due = [r["due"] for r in a]
    assert len(a) == round(mix["rate_rps"] * 10.0)
    assert due == sorted(due) and 0.0 <= due[0] and due[-1] < 10.0
    for field, spec in mix["fields"].items():
        got = [r[field] for r in a]
        want = traffic.exact_counts(spec.get("weights", [1] * len(
            spec["values"])), len(a))
        assert [got.count(v) for v in spec["values"]] == list(want)
        assert got != sorted(got)
    other = traffic.open_loop(dict(mix, work_seed=mix["work_seed"] + 1), 10.0)
    assert not np.allclose(due, [r["due"] for r in other])


def test_missing_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no peaks"):
        harness.peaks("some other chip")


def _run(cwd, env=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env or {}))
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "qwen2-0.5b.split-poisson", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_refuses_without_a_tpu():
    out = _run(REPO)
    assert out.returncode != 0
    assert "needs a TPU" in out.stderr
    assert out.stdout.strip() == ""


def test_refuses_with_only_the_benchmark_files(tmp_path):
    shutil.copytree(harness.ROOT, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    out = _run(tmp_path)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
