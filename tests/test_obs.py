"""repro.obs: null-recorder overhead contract, JSONL schema round-trip,
nested-span structure, metrics, report folding, the bit-identity
invariant (recording must not change results), and the JAX retrace
accounting — zero re-traces across param hot-swaps, exactly one on a
genuine shape change."""
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import make_paper_env
from repro.core.env import env_reset
from repro.obs import (NullRecorder, Recorder, SCHEMA_VERSION, jaxmon,
                       read_events, recording, report)
from repro.obs.metrics import Metrics
from repro.policies import build_policy
from repro.scenarios import get_scenario, run_scenario


# --------------------------------------------------------------------------
# null default + recorder lifecycle
# --------------------------------------------------------------------------

def test_null_recorder_is_default_and_noop():
    rec = obs.get_recorder()
    assert isinstance(rec, NullRecorder) and not rec.enabled
    # the disabled span is one shared object: no allocation per use
    s1, s2 = obs.span("a", x=1), obs.span("b")
    assert s1 is s2
    with s1:
        pass
    obs.event("nothing", y=2)                      # no-op, no error
    obs.inc("c"), obs.observe("h", 2.0)


def test_recording_installs_and_restores(tmp_path):
    before = obs.get_recorder()
    with recording(str(tmp_path / "e.jsonl")) as rec:
        assert obs.get_recorder() is rec and rec.enabled
        obs.event("inside")
    assert obs.get_recorder() is before
    # close() wrote the file and is idempotent
    rec.close()
    meta, events = read_events(str(tmp_path / "e.jsonl"))
    assert meta["schema"] == SCHEMA_VERSION
    assert any(e["type"] == "event" and e["name"] == "inside"
               for e in events)


def test_schema_round_trip(tmp_path):
    path = str(tmp_path / "events.jsonl")
    with recording(path, meta={"tool": "test", "n": 3}) as rec:
        with obs.span("outer", k="v"):
            obs.event("point", val=np.float64(1.5))
        rec.metrics.inc("hits", 2.0)
    meta, events = read_events(path)
    assert meta["type"] == "meta" and meta["clock"] == "perf_counter"
    assert meta["meta"] == {"tool": "test", "n": 3}
    types = {e["type"] for e in events}
    assert {"span", "event", "metric"} <= types
    # numpy attrs serialized as plain JSON scalars
    point = next(e for e in events if e.get("name") == "point")
    assert point["attrs"]["val"] == 1.5
    # seq is a total order
    seqs = [e["seq"] for e in events]
    assert seqs == sorted(seqs) == list(range(len(events)))


def test_read_events_rejects_foreign_files(tmp_path):
    p = tmp_path / "x.jsonl"
    p.write_text('{"not": "meta"}\n')
    with pytest.raises(ValueError, match="no meta header"):
        read_events(str(p))
    p.write_text(json.dumps({"type": "meta", "schema": 999}) + "\n")
    with pytest.raises(ValueError, match="schema"):
        read_events(str(p))


def test_nested_spans_depth_parent_ordering():
    rec = Recorder()
    with rec.span("a"):
        with rec.span("b", tag=1):
            pass
        with rec.span("c"):
            pass
    spans = [e for e in rec.events if e["type"] == "span"]
    # spans emit at exit: children precede the parent in the stream
    assert [s["name"] for s in spans] == ["b", "c", "a"]
    b, c, a = spans
    assert b["depth"] == c["depth"] == 1 and a["depth"] == 0
    assert b["parent"] == c["parent"] == "a" and a["parent"] is None
    assert b["attrs"] == {"tag": 1}
    # children are timed within the parent window
    assert a["t"] <= b["t"] and b["t"] + b["dur"] <= a["t"] + a["dur"] + 1e-9


def test_span_attr_may_be_called_name():
    rec = Recorder()
    rec.event("drift.regime_switch", name="brownout")   # no collision
    with rec.span("s", name="inner"):
        pass
    assert rec.events[0]["attrs"] == {"name": "brownout"}


# --------------------------------------------------------------------------
# metrics
# --------------------------------------------------------------------------

def test_metrics_counters_gauges_histograms():
    m = Metrics()
    m.inc("req", 2.0, policy="a2c")
    m.inc("req", 3.0, policy="a2c")
    m.inc("req", 1.0, policy="greedy")
    for v in range(1, 101):
        m.observe("lat", float(v))
    snap = {(s["name"], tuple(sorted(s.get("labels", {}).items()))): s
            for s in m.snapshot()}
    assert snap[("req", (("policy", "a2c"),))]["value"] == 5.0
    assert snap[("req", (("policy", "greedy"),))]["value"] == 1.0
    h = snap[("lat", ())]
    assert h["kind"] == "histogram" and h["count"] == 100
    assert h["min"] == 1.0 and h["max"] == 100.0
    assert h["p50"] == pytest.approx(50.5, abs=1.0)
    assert h["p99"] == pytest.approx(99.0, abs=1.5)


def test_module_metrics_route_to_active_recorder(tmp_path):
    path = str(tmp_path / "m.jsonl")
    with recording(path):
        obs.inc("fleet.arrivals", 7, policy="x")
        obs.observe("q", 1.0)
    _, events = read_events(path)
    ms = [e for e in events if e["type"] == "metric"]
    names = {m["name"] for m in ms}
    assert {"fleet.arrivals", "q"} <= names


# --------------------------------------------------------------------------
# report folding
# --------------------------------------------------------------------------

def test_report_fold_and_render(tmp_path):
    path = str(tmp_path / "r.jsonl")
    with recording(path, meta={"tool": "test"}):
        for i in range(3):
            with obs.span("fleet.epoch", epoch=i):
                with obs.span("fleet.decide"):
                    pass
        obs.event("drift.trigger", n=1)
        obs.event("online.hotswap", epoch=2)
        obs.inc("served", 10)
    rep = report.load(path)
    assert rep["phases"]["fleet.epoch"]["count"] == 3
    assert rep["phases"]["fleet.decide"]["count"] == 3
    assert rep["phases"]["fleet.epoch"]["total_s"] >= \
        rep["phases"]["fleet.decide"]["total_s"]
    assert [e["name"] for e in rep["timeline"]] == ["drift.trigger",
                                                    "online.hotswap"]
    assert rep["wall_s"] > 0
    text = report.render(rep)
    for needle in ("per-phase timing:", "fleet.epoch",
                   "drift/online timeline:", "drift.trigger", "metrics:"):
        assert needle in text
    # folded report is JSON-serializable as obsview --json writes it
    json.dumps(rep, default=str)


def test_structured_logging_gates_console(capsys, tmp_path):
    old = obs.get_verbosity()
    try:
        obs.set_verbosity(0)
        with recording(str(tmp_path / "l.jsonl")):
            obs.info("hidden info")
            obs.debug("hidden debug")
            obs.warn("visible warn")
        out = capsys.readouterr()
        assert "hidden" not in out.out and "hidden" not in out.err
        assert "visible warn" in out.err
        # --quiet console still records the full story
        _, events = read_events(str(tmp_path / "l.jsonl"))
        logged = {(e["level"], e["msg"]) for e in events
                  if e["type"] == "log"}
        assert {("info", "hidden info"), ("debug", "hidden debug"),
                ("warn", "visible warn")} <= logged
        obs.set_verbosity(2)
        obs.info("now info")
        obs.debug("now debug")
        out = capsys.readouterr()
        assert "now info" in out.out and "now debug" in out.out
    finally:
        obs.set_verbosity(old)


# --------------------------------------------------------------------------
# bit-identity: recording must not change results
# --------------------------------------------------------------------------

def test_comparison_report_bit_identical_on_vs_off(tmp_path):
    sc = get_scenario("paper-exact")
    roster = ("greedy_oracle", "device_only")
    kw = dict(n_requests=1200, seeds=(0,))
    off = run_scenario(sc, roster, **kw)
    with recording(str(tmp_path / "t.jsonl")):
        on = run_scenario(sc, roster, **kw)
    assert off.to_json() == on.to_json()


# --------------------------------------------------------------------------
# jax accounting: compile listeners + retrace counters
# --------------------------------------------------------------------------

def test_track_compiles_counts_fresh_compiles_only():
    jaxmon.install()

    @jax.jit
    def f(x):
        return x * 2.0

    with jaxmon.track_compiles() as d1:
        f(jnp.ones(3))
    assert d1.get("backend_compile_n", 0) >= 1
    with jaxmon.track_compiles() as d2:
        f(jnp.ones(3))                       # cache hit
    assert d2 == {}


def test_count_trace_fires_at_trace_time_only():
    site = "test.count_trace_site"
    jaxmon.reset_trace_counts()

    @jax.jit
    def g(x):
        jaxmon.count_trace(site)
        return x + 1

    with jaxmon.track_traces() as d:
        g(jnp.ones(4))
        g(jnp.ones(4))                       # cache hit: body not re-run
        g(jnp.ones(5))                       # new shape: one re-trace
    assert d[site] == 2


@pytest.fixture(scope="module")
def tiny_trained_a2c():
    cfg, tables = make_paper_env(n_uavs=3, slot_seconds=10.0,
                                 peak_rps=20.0)
    pol = build_policy("a2c", cfg, tables, episodes=2)
    pol.train(seed=0)
    return cfg, tables, pol


def test_zero_retraces_on_param_hotswap(tiny_trained_a2c):
    cfg, tables, pol = tiny_trained_a2c
    state = env_reset(cfg, tables, jax.random.key(0))
    k = jax.random.key(1)
    site = f"decide.{pol.name}"
    with jaxmon.track_traces() as d:
        first = np.asarray(pol.jitted()(state, k))
    assert d.get(site, 0) == 1
    # hot-swap params repeatedly: the compiled decide re-binds, and the
    # measured invariant is that it never re-traces
    with jaxmon.track_traces() as d:
        for i in range(5):
            bumped = jax.tree.map(lambda x: x + 0.01, pol.params)
            pol.set_params(bumped)
            out = np.asarray(pol.jitted()(state, k))
    assert site not in d, f"param hot-swap re-traced: {d}"
    assert out.shape == first.shape


def test_exactly_one_retrace_on_genuine_shape_change(tiny_trained_a2c):
    cfg, tables, pol = tiny_trained_a2c
    state = env_reset(cfg, tables, jax.random.key(0))
    k = jax.random.key(1)
    site = f"decide.{pol.name}"
    base = np.asarray(pol.jitted()(state, k))        # warm current params
    # queue is a scalar in env_reset; a per-device (n,) zeros vector is
    # numerically identical after _obs_features' broadcast but is a
    # different abstract shape — the one legitimate re-trace
    wide = dict(state, queue=jnp.zeros(cfg.n_uavs, jnp.float32))
    with jaxmon.track_traces() as d:
        out = np.asarray(pol.jitted()(wide, k))
        np.asarray(pol.jitted()(wide, k))            # now cached again
    assert d.get(site, 0) == 1, f"expected exactly one re-trace: {d}"
    np.testing.assert_array_equal(base, out)


def test_online_run_traces_once_per_exploration_rate():
    """The closed-loop acceptance invariant: across a whole online
    adaptation run — bursts, window updates, param hot-swaps every few
    epochs — the decide site traces exactly once per exploration rate
    (greedy + the burst epsilon), never per swap."""
    from repro.online import OnlineConfig, get_schedule
    from repro.sim import FleetConfig, PoissonTrace, simulate

    cfg, tables = make_paper_env(n_uavs=3, slot_seconds=10.0,
                                 peak_rps=20.0)
    trace = PoissonTrace(rate_rps=6.0)
    pol = build_policy("a2c", cfg, tables, episodes=2)
    pol.train(seed=0)
    oc = OnlineConfig(algo="a2c", gate="always", window=16, min_window=4,
                      update_every=1)
    site = f"decide.{pol.name}"
    with jaxmon.track_traces() as d:
        res = simulate(cfg, tables, pol, trace, n_requests=6000, seed=0,
                       fleet=FleetConfig(slo_s=1.0),
                       schedule=get_schedule("link-brownout", onset=5,
                                             recover=0),
                       online=oc)
    assert res.adaptation["online"]["updates"] > 1   # swaps happened
    eps_rates = {0.0, oc.explore_eps}
    assert d.get(site, 0) <= len(eps_rates), \
        f"decide re-traced beyond once-per-eps: {d}"


# --------------------------------------------------------------------------
# bench harness: repeated samples ride along in the records
# --------------------------------------------------------------------------

def _load_bench_module():
    path = os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                        "run.py")
    spec = importlib.util.spec_from_file_location("bench_run", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_timeit_reports_samples():
    from repro.bench import runner as brunner
    bench = _load_bench_module()
    t = bench._timeit(lambda: jnp.ones(8), n=2, reps=4)
    assert isinstance(t, float) and len(t.samples) == 4
    assert float(t) == min(t.samples)
    sink = brunner.Sink(echo=False)
    sink.row("x", t, "d")
    sink.row("y", 12.34, "single-sample rows keep working")
    rx, ry = sink.records
    assert rx["samples"] == [float(f"{s:.4g}") for s in t.samples]
    assert rx["us_per_call"] == rx["min"]
    assert rx["mean"] >= rx["min"] and rx["std"] >= 0.0
    assert ry["name"] == "y" and ry["us_per_call"] == 12.34
    assert ry["samples"] == [12.34] and ry["n"] == 1
    assert ry["ci_lo"] == ry["ci_hi"] == 12.34
