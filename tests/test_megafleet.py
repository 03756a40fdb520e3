"""repro.sim.megafleet: the vectorized fleet engines.

The load-bearing guarantee: ``engine="vectorized"`` is *bit-identical*
to the ``engine="loop"`` oracle under the same seed — same rng stream
consumption, same padded-Lindley arithmetic, same device-order metric
recording — across stationary presets AND a drift schedule. The scan
engine trades bitwise parity for a fused jit (jax PRNG for world noise,
float32, histogram percentiles), so its contract is determinism +
statistical agreement + identical workload accounting.
"""
import numpy as np
import pytest

from repro.policies import build_policy
from repro.scenarios import get_scenario
from repro.sim import (AnalyticalBackend, EpochLog, FleetConfig,
                       presample_counts, simulate)


def _world(preset):
    sc = get_scenario(preset)
    env_cfg, tables, model_ids, bf = sc.build_env()
    return sc, env_cfg, tables, model_ids, bf


def _run(sc, env_cfg, tables, model_ids, bf, policy, engine, *,
         n_requests, seed=0, schedule=None, **fl_kw):
    fl = FleetConfig(slo_s=sc.slo_s, engine=engine, **fl_kw)
    backend = bf() if engine != "scan" else None
    return simulate(env_cfg, tables, policy, sc.build_trace(),
                    n_requests=n_requests, seed=seed, fleet=fl,
                    backend=backend, model_ids=model_ids,
                    schedule=schedule)


def _assert_bit_identical(a, b):
    assert np.array_equal(a.selection_hist, b.selection_hist)
    assert a.served == b.served
    assert a.epochs == b.epochs
    assert a.metrics.dropped == b.metrics.dropped
    assert np.array_equal(a.metrics.latencies_s, b.metrics.latencies_s)
    assert np.array_equal(a.metrics.energies_j, b.metrics.energies_j)
    assert np.array_equal(a.metrics.devices, b.metrics.devices)
    assert a.summary == b.summary
    assert list(a.epoch_log) == list(b.epoch_log)


# --------------------------------------------------------------------------
# loop vs vectorized: bit-exact parity
# --------------------------------------------------------------------------

@pytest.mark.parametrize("preset,policy_name", [
    ("diurnal-fleet", "device_only"),
    ("degraded-link", "greedy_oracle"),
    ("paper-mmpp-burst", "full_offload"),
])
def test_vectorized_matches_loop_bitexact(preset, policy_name):
    sc, env_cfg, tables, mids, bf = _world(preset)
    pol = build_policy(policy_name, env_cfg, tables)
    a = _run(sc, env_cfg, tables, mids, bf, pol, "loop",
             n_requests=4000, seed=3)
    b = _run(sc, env_cfg, tables, mids, bf, pol, "vectorized",
             n_requests=4000, seed=3)
    assert a.served >= 4000
    _assert_bit_identical(a, b)


def test_vectorized_matches_loop_under_drift():
    """The regime-switch path (cached per-regime backends, trace
    scaling, battery side effects) stays bit-identical too."""
    sc, env_cfg, tables, mids, bf = _world("link-brownout")
    pol = build_policy("device_only", env_cfg, tables)
    sched = sc.build_schedule()
    a = _run(sc, env_cfg, tables, mids, bf, pol, "loop",
             n_requests=40_000, seed=1, schedule=sched)
    b = _run(sc, env_cfg, tables, mids, bf, pol, "vectorized",
             n_requests=40_000, seed=1, schedule=sched)
    assert {e["regime"] for e in a.epoch_log} >= {0, 1}  # drift crossed
    _assert_bit_identical(a, b)
    assert a.adaptation == b.adaptation


def test_vectorized_matches_loop_with_dead_devices():
    """Dead devices must keep consuming the offset draws (stream-order
    invariance) while their arrivals drop — on both engines alike. The
    device-churn schedule kills devices 0-1 deterministically."""
    sc, env_cfg, tables, mids, bf = _world("device-churn")
    pol = build_policy("device_only", env_cfg, tables)
    sched = sc.build_schedule()
    a = _run(sc, env_cfg, tables, mids, bf, pol, "loop",
             n_requests=30_000, seed=0, schedule=sched)
    b = _run(sc, env_cfg, tables, mids, bf, pol, "vectorized",
             n_requests=30_000, seed=0, schedule=sched)
    assert a.metrics.dropped > 0
    _assert_bit_identical(a, b)


def test_selection_hist_is_int64_and_accounts_every_request():
    sc, env_cfg, tables, mids, bf = _world("diurnal-fleet")
    pol = build_policy("device_only", env_cfg, tables)
    r = _run(sc, env_cfg, tables, mids, bf, pol, "vectorized",
             n_requests=3000)
    assert r.selection_hist.dtype == np.int64
    assert r.selection_hist.sum() == r.served - r.metrics.dropped


# --------------------------------------------------------------------------
# scan engine
# --------------------------------------------------------------------------

def test_scan_deterministic_and_close_to_vectorized():
    sc, env_cfg, tables, mids, bf = _world("diurnal-fleet")
    pol = build_policy("device_only", env_cfg, tables)
    s1 = _run(sc, env_cfg, tables, mids, bf, pol, "scan",
              n_requests=15_000)
    s2 = _run(sc, env_cfg, tables, mids, bf, pol, "scan",
              n_requests=15_000)
    assert s1.summary == s2.summary
    assert np.array_equal(s1.selection_hist, s2.selection_hist)

    v = _run(sc, env_cfg, tables, mids, bf, pol, "vectorized",
             n_requests=15_000)
    # identical workload accounting: the trace rng stream is shared, so
    # epochs/served match exactly; a static policy picks identical
    # actions, so the selection histogram matches exactly too
    assert s1.epochs == v.epochs
    assert s1.served == v.served
    assert np.array_equal(s1.selection_hist, v.selection_hist)
    # world noise comes from a jax PRNG instead of the numpy stream, so
    # metric agreement is statistical (f32 + log-binned percentiles)
    assert abs(s1.summary["slo_attainment"]
               - v.summary["slo_attainment"]) < 0.05
    assert s1.summary["mean"] == pytest.approx(v.summary["mean"],
                                               rel=0.15)
    assert s1.summary["energy_j"] == pytest.approx(
        v.summary["energy_j"], rel=0.01)
    assert len(s1.epoch_log) == s1.epochs
    assert s1.epoch_log[0]["arrivals"] == v.epoch_log[0]["arrivals"]


def test_scan_shard_matches_unsharded():
    """shard=True over a 1-device mesh must be bit-identical to
    shard=False (per-shard noise keys fold in the shard index; the
    unsharded path folds index 0)."""
    sc, env_cfg, tables, mids, bf = _world("diurnal-fleet")
    pol = build_policy("device_only", env_cfg, tables)
    a = _run(sc, env_cfg, tables, mids, bf, pol, "scan", n_requests=6000)
    b = _run(sc, env_cfg, tables, mids, bf, pol, "scan", n_requests=6000,
             shard=True)
    assert a.summary == b.summary
    assert np.array_equal(a.selection_hist, b.selection_hist)


def test_scan_shard_over_four_virtual_devices():
    """shard=True over a 4-device mesh (virtual CPU devices, so in a
    child process): exact workload accounting against shard=False."""
    import os
    import subprocess
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env["PYTHONPATH"] = os.path.join(repo, "src") + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    code = ("import chip_smoke; "
            "chip_smoke.phase_fleet_sharded(4, devices=2002, epochs=3)")
    out = subprocess.run([sys.executable, "-c", code], cwd=repo, env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "shard=True (mesh of 4)" in out.stdout


def test_scan_rejects_unsupported_modes():
    sc, env_cfg, tables, mids, bf = _world("link-brownout")
    pol = build_policy("device_only", env_cfg, tables)
    with pytest.raises(ValueError, match="stationary"):
        _run(sc, env_cfg, tables, mids, bf, pol, "scan",
             n_requests=1000, schedule=sc.build_schedule())
    with pytest.raises(ValueError, match="valid engines"):
        _run(sc, env_cfg, tables, mids, bf, pol, "warp", n_requests=1000)
    with pytest.raises(ValueError, match="shard"):
        _run(sc, env_cfg, tables, mids, bf, pol, "loop",
             n_requests=1000, shard=True)


# --------------------------------------------------------------------------
# satellites: presample, EpochLog, per-regime backend cache
# --------------------------------------------------------------------------

def test_presample_counts_matches_stream():
    sc = get_scenario("diurnal-fleet")
    trace = sc.build_trace()
    r1 = np.random.default_rng(7)
    counts = presample_counts(trace, r1, 8, sc.slot_seconds, 5000, 1000)
    r2 = np.random.default_rng(7)
    stream = trace.stream(r2, 8, sc.slot_seconds)
    served = 0
    for t in range(counts.shape[0]):
        assert np.array_equal(counts[t], next(stream))
        served += int(counts[t].sum())
    assert served >= 5000
    assert int(counts[:-1].sum()) < 5000   # stops at the crossing epoch


def test_epoch_log_dict_view():
    log = EpochLog()
    for i in range(20):
        log.append({"epoch": i, "arrivals": 10 * i, "queue_jobs": 0.5 * i})
    assert len(log) == 20 and bool(log)
    assert log[0] == {"epoch": 0, "arrivals": 0, "queue_jobs": 0.0}
    assert log[-1]["epoch"] == 19
    assert [e["arrivals"] for e in log[5:8]] == [50, 60, 70]
    assert sum(e["epoch"] for e in log) == sum(range(20))
    assert log.column("arrivals").dtype == np.int64
    assert isinstance(log[3]["queue_jobs"], float)
    with pytest.raises(IndexError):
        log[20]
    assert not EpochLog()


def test_epoch_log_stride_and_cap():
    log = EpochLog(stride=3, cap=4)
    for i in range(30):
        log.append({"epoch": i})
    assert [e["epoch"] for e in log] == [0, 3, 6, 9]
    bulk = EpochLog(stride=3, cap=4)
    bulk.extend_columns(epoch=np.arange(30))
    assert [e["epoch"] for e in bulk] == [e["epoch"] for e in log]
    with pytest.raises(ValueError):
        EpochLog(stride=0)


def test_fleet_log_stride_and_cap_thread_through():
    sc, env_cfg, tables, mids, bf = _world("diurnal-fleet")
    pol = build_policy("device_only", env_cfg, tables)
    full = _run(sc, env_cfg, tables, mids, bf, pol, "vectorized",
                n_requests=8000)
    strided = _run(sc, env_cfg, tables, mids, bf, pol, "vectorized",
                   n_requests=8000, log_stride=2, log_cap=2)
    assert full.epochs >= 4
    assert [e["epoch"] for e in strided.epoch_log] == [0, 2]
    assert strided.summary == full.summary   # logging never alters physics


def test_schedule_compile_caches_backends():
    sc = get_scenario("link-brownout")
    env_cfg, tables, mids, bf = sc.build_env()
    sched = sc.build_schedule()
    regimes = sched.compile(env_cfg, tables)
    assert regimes[0].backend is None          # base: fleet's own backend
    patched = [r for r in regimes if r.env_cfg is not env_cfg]
    assert patched, "schedule has no patched regime to cache for"
    for r in patched:
        assert isinstance(r.backend, AnalyticalBackend)
        assert r.backend.env_cfg is r.env_cfg
    # tables-less compile (older call sites) stays backend-free
    assert all(r.backend is None for r in sched.compile(env_cfg))
