"""CPU rehearsal of each driver through the harness, at the program's
reduced sizes with the kernels in interpret mode and a window of about a
second: the contract line's shape, and the correctness comparison with
the timed path sound and with it broken where it produces its answers.

The test steers the run itself (``run.measure`` with ``reduced=True``
and CPU devices); no command-line switch lets a CPU run print device
metrics.
"""
import os
import sys
import time
from pathlib import Path

# the benchmark's modules and the program, CPU only (no conftest here:
# its module name would collide with the repository's tests/conftest.py)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
for _p in (Path(__file__).resolve().parents[1],
           Path(__file__).resolve().parents[3] / "src"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

import numpy as np
import pytest

import harness
import run as entry

PEAKS = {"bf16_flops": 197e12, "int8_ops": 393e12, "hbm_bytes_per_s": 819e9}
KEYS = {"correct", "attempted", "failed", "metrics", "device", "checks"}


@pytest.fixture
def interpret(monkeypatch):
    monkeypatch.setenv("REPRO_USE_PALLAS", "interpret")


def small_cell(name="qwen2-0.5b.split-poisson"):
    """The cell with its mix cut to CPU sizes (lengths, rate, sample)."""
    import jax
    cell = harness.find_cell(name)
    mix = cell.mix
    mix["fields"]["prompt_len"]["values"] = [16, 32]
    mix.update(rate_rps=12, trace_start_s=0.2, trace_seconds=0.5)
    mix["check"]["per_version"] = 4
    return cell, jax.devices()


def measure(name="qwen2-0.5b.split-poisson", trace=False, seconds=1.0):
    cell, devs = small_cell(name)
    return entry.measure(cell, 2 ** 31 + 5, seconds, trace, devs, PEAKS,
                         reduced=True, t_start=0.0)


@pytest.mark.parametrize("name,e2e", [
    ("qwen2-0.5b.split-poisson", {"infer_p50_ms", "infer_p95_ms",
                                  "setup_s"}),
])
def test_contract_line(interpret, name, e2e):
    result, lines, _ = measure(name)
    assert KEYS <= set(result)
    assert set(result["metrics"]) == e2e
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["device"]["platform"] == "cpu"
    assert result["correct"], lines
    assert list(result)[-1] == "checks"
    for n, v, lim in lines:
        assert result["checks"][n] == {"value": v, "limit": lim}


def test_traced_split_run_reports_layers(interpret):
    result, _, _ = measure("qwen2-0.5b.split-poisson", trace=True)
    assert result["correct"]
    m = result["metrics"]
    assert m["compiles_in_window.split"]["value"] == 0
    assert "service_ms.split" in m and "idle_share.split" in m
    assert m["req_p95_ms.split"]["value"] >= m["service_ms.split"]["value"]
    assert {"busy_s", "window_s"} <= set(result["device"])
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}


def test_split_answer_altered_is_not_correct(interpret, monkeypatch):
    from repro.serving import SplitServingEngine

    infer = SplitServingEngine.infer

    def altered(self, batch, cut, version="bf16"):
        logits, nbytes = infer(self, batch, cut, version)
        last = logits[0, -1]
        other = (np.asarray(last).argmax() + 7) % last.shape[-1]
        return logits.at[0, -1, other].add(1e3), nbytes

    monkeypatch.setattr(SplitServingEngine, "infer", altered)
    result, lines, _ = measure("qwen2-0.5b.split-poisson")
    assert not result["correct"], lines


def test_split_control_is_not_correct(interpret):
    """The control in the program's place (each sampled answer replaced
    by the reference one precision step down: bfloat16 for the float32
    versions, int4 weights for w8), then the driver's own numbers and the
    cell's own comparison: the control fails it, and its relative error
    is over ten times the program's on each version. On the CPU the program runs float32 in full, so its
    own numbers stay near rounding; whether the chip's limits separate
    the bfloat16 step at full size is read on the chip (PERF.md)."""
    import calibrate

    cell, _ = small_cell()
    cell.mix["rate_rps"] = 24
    drv = cell.driver
    st = drv.setup(cell, 2 ** 31 + 9, harness.Spans(), reduced=True)
    drv.window(st, 1.5, None, harness.Compiles.install())
    prog, ctl, passes, _ = calibrate.control(st, cell.limits)
    assert not passes
    assert set(prog) == set(ctl) == {f"{n}.{v}" for n in ("gap", "rel_err")
                                     for v in drv.VERSIONS}
    for v in drv.VERSIONS:
        assert ctl[f"rel_err.{v}"] > 10 * prog[f"rel_err.{v}"], v


def test_sample_takes_each_version_and_its_longest(interpret):
    cell, _ = small_cell()
    cell.mix["rate_rps"] = 24
    drv = cell.driver
    st = drv.setup(cell, 2 ** 31 + 10, harness.Spans(), reduced=True)
    drv.window(st, 1.5, None, harness.Compiles.install())
    groups = drv.sampled(st)
    assert set(groups) == set(drv.VERSIONS)
    for v, group in groups.items():
        served = [r for r in st.reqs if r["version"] == v]
        assert len(group) == min(4, len(served))
        assert all(r["version"] == v for r in group)
        assert max(r["prompt_len"] for r in group) == max(
            r["prompt_len"] for r in served)


def test_version_trees_come_from_the_program(interpret):
    """Set-up fills every version, bf16 included, from the program's
    build_version_params in one call, and assigns none itself."""
    cell, _ = small_cell()
    st = cell.driver.setup(cell, 3, harness.Spans(), reduced=True)
    assert set(st.engine._vparams) == set(cell.driver.VERSIONS)


def test_engine_without_version_cache_fails_loudly(monkeypatch):
    from repro.serving import SplitServingEngine

    init = SplitServingEngine.__init__

    def no_cache(self, *a, **kw):
        init(self, *a, **kw)
        del self._vparams

    monkeypatch.setattr(SplitServingEngine, "__init__", no_cache)
    cell, _ = small_cell()
    with pytest.raises(RuntimeError, match="_vparams"):
        cell.driver.setup(cell, 3, harness.Spans(), reduced=True)


def test_each_request_runs_with_its_due_slot_decision(interpret,
                                                      monkeypatch):
    """A request's (version, cut) is its device's decision for the slot it
    is due in, however far serving runs behind."""
    cell, _ = small_cell()
    cell.mix["rate_rps"] = 8
    drv = cell.driver
    seed = 2 ** 31 + 11
    compiles = harness.Compiles.install()
    st = drv.setup(cell, seed, harness.Spans(), reduced=True)
    drv.window(st, 2.5, None, compiles)
    first = [(r["version"], r["cut"]) for r in st.reqs]

    serve = drv._serve

    def slow(*args):
        time.sleep(0.15)
        return serve(*args)

    monkeypatch.setattr(drv, "_serve", slow)
    drv.reseed(st, seed)
    t0 = drv.window(st, 2.5, None, compiles)
    assert any(int(r["start"] - t0) > int(r["due"]) for r in st.reqs)
    assert [(r["version"], r["cut"]) for r in st.reqs] == first
