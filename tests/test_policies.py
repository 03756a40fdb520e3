"""repro.policies: canonical registry semantics, the PPO path exposed
end-to-end, and trained-policy artifacts (train → save → load → act
bit-identical)."""
import jax
import numpy as np
import pytest

from repro.core import make_paper_env
from repro.policies import (A2CPolicy, build_policy, get_policy_spec,
                            policy_names)
from repro.scenarios import get_scenario, run_scenario
from repro.sim.traces import RandomRateTrace


# --------------------------------------------------------------------------
# registry: one canonical name per policy, clear misses
# --------------------------------------------------------------------------

def test_registry_has_canonical_roster():
    names = policy_names()
    for name in ("a2c", "ppo", "greedy_oracle", "device_only",
                 "full_offload", "random"):
        assert name in names, names
    assert get_policy_spec("a2c").trainable
    assert get_policy_spec("ppo").trainable
    assert not get_policy_spec("greedy_oracle").trainable


def test_registry_miss_lists_valid_names():
    """The historical 'oracle' alias is gone: one canonical name per
    policy, and a miss names every valid one."""
    with pytest.raises(KeyError) as e:
        get_policy_spec("oracle")
    msg = str(e.value)
    for name in policy_names():
        assert name in msg
    with pytest.raises(KeyError):
        build_policy("no-such-policy", *make_paper_env())


def test_static_policy_has_no_artifact_lifecycle():
    cfg, tables = make_paper_env()
    pol = build_policy("device_only", cfg, tables)
    with pytest.raises(NotImplementedError):
        pol.save("/tmp/unused.npz")
    with pytest.raises(NotImplementedError):
        pol.train()


@pytest.mark.parametrize("name", ["device_only", "full_offload", "random",
                                  "greedy_oracle"])
def test_static_policy_acts_on_a_fleet_shard(name):
    """A static policy sizes its actions from the state it is given, so
    the sharded scan engine can run it on one shard of the fleet."""
    from repro.core.controller import measured_state
    env_cfg, tables = make_paper_env(n_uavs=8)
    n = 3
    state = measured_state(env_cfg, tables, battery_j=np.full(n, 1e4),
                           bandwidth=np.full(n, 1e7), p_tx=np.full(n, 1.0),
                           queue_jobs=0.0, load=np.full(n, 0.5))
    pol = build_policy(name, env_cfg, tables)
    assert pol.act(state, jax.random.key(0)).shape == (n, 2)


def test_untrained_policy_refuses_to_act():
    cfg, tables = make_paper_env()
    pol = build_policy("a2c", cfg, tables, episodes=1)
    state = {"model_id": np.zeros(cfg.n_uavs, np.int32)}
    with pytest.raises(RuntimeError, match="train"):
        pol.act(state, jax.random.key(0))


# --------------------------------------------------------------------------
# PPO exposed end-to-end: registry -> scenario -> paired mmpp comparison
# --------------------------------------------------------------------------

def test_ppo_mmpp_comparison_smoke():
    """PPO trains (trace-driven, like A2C) and runs through the same
    scenario entry point as every other policy, paired request streams
    included."""
    sc = get_scenario("paper-mmpp-burst")
    rep = run_scenario(sc, ("ppo", "device_only"), n_requests=1200,
                       seeds=(0,), episodes=3)
    ppo, dev = rep.results["ppo"], rep.results["device_only"]
    assert ppo.trained and not dev.trained
    # same seed -> identical offered request stream (paired comparison)
    assert ppo.per_seed[0]["requests"] == dev.per_seed[0]["requests"]
    for r in (ppo, dev):
        assert np.isfinite(r.mean["p95"])
        assert 0.0 <= r.mean["slo_attainment"] <= 1.0


# --------------------------------------------------------------------------
# artifacts: train -> save -> load -> act, bit-identical
# --------------------------------------------------------------------------

def _some_states(cfg, tables, n=4):
    from repro.core import env_reset
    return [env_reset(cfg, tables, jax.random.key(1000 + i))
            for i in range(n)]


@pytest.mark.parametrize("name,batch_envs", [("a2c", 1), ("a2c", 2),
                                             ("ppo", 1)])
def test_checkpoint_round_trip_bit_identical(tmp_path, name, batch_envs):
    """A policy trained with any batch_envs setting saves one artifact
    that reloads into a fresh instance and reproduces bit-identical
    actions under the same rng."""
    cfg, tables = make_paper_env(peak_rps=20.0)
    trained = build_policy(name, cfg, tables, episodes=3,
                           batch_envs=batch_envs)
    trained.train(seed=0, trace=RandomRateTrace(max_rps=20.0))
    path = str(tmp_path / f"{name}_E{batch_envs}.npz")
    trained.save(path)

    fresh = build_policy(name, cfg, tables, episodes=3,
                         batch_envs=batch_envs)
    fresh.load(path)
    for state in _some_states(cfg, tables):
        rng = jax.random.key(7)
        np.testing.assert_array_equal(
            np.asarray(trained.act(state, rng)),
            np.asarray(fresh.act(state, rng)))


def test_load_retraces_the_jitted_decide(tmp_path):
    """``Policy.jitted`` must not serve a decide step compiled against
    stale params after load() swaps them."""
    cfg, tables = make_paper_env()
    pol = build_policy("a2c", cfg, tables, episodes=2)
    pol.train(seed=0)
    before = pol.jitted()
    assert pol.jitted() is before          # stable while params are
    path = str(tmp_path / "ctrl.npz")
    pol.save(path)
    pol.load(path)
    assert pol.jitted() is not before      # params swapped -> re-traced


def test_artifact_refuses_wrong_policy_and_env(tmp_path):
    cfg, tables = make_paper_env(peak_rps=20.0)
    # directly-constructed (not registry-built) policies carry the same
    # canonical name, so their artifacts interoperate with build_policy
    a2c = A2CPolicy(cfg, tables, episodes=2)
    assert a2c.name == "a2c"
    a2c.train(seed=0)
    path = str(tmp_path / "ctrl.npz")
    a2c.save(path)
    loaded = build_policy("a2c", cfg, tables, episodes=2).load(path)
    state = _some_states(cfg, tables, n=1)[0]
    np.testing.assert_array_equal(
        np.asarray(a2c.act(state, jax.random.key(0))),
        np.asarray(loaded.act(state, jax.random.key(0))))
    # wrong algorithm: meta check (match the quoted algo, not the path)
    with pytest.raises(ValueError, match="holds a 'a2c'"):
        build_policy("ppo", cfg, tables, episodes=2).load(path)
    # wrong fleet size: structure/shape check
    cfg6, tables6 = make_paper_env(n_uavs=6, peak_rps=20.0)
    with pytest.raises(ValueError):
        build_policy("a2c", cfg6, tables6, episodes=2).load(path)
