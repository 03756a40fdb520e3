"""EdgeRL controller: the centralized decision-maker (paper Sec. II-D).

Wires profiles -> env -> A2C and exposes:
  - ``make_paper_env``: the faithful testbed (VGG/ResNet/DenseNet on
    Jetson-TX2-class UAVs + PowerEdge-class edge server).
  - ``make_tpu_env``: the TPU adaptation (assigned transformer archs;
    device/server = head/tail submesh with roofline-derived throughputs,
    ICI link as the uplink) — see DESIGN.md §2.
  - ``train_agent`` / ``evaluate_policy`` / ``decide``.
"""
from __future__ import annotations

from typing import Dict, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import a2c as A2C
from repro.core.env import (EnvConfig, ProfileTables, build_tables,
                            env_reset, env_step, observe)
from repro.core.latency import LatencyParams
from repro.core.energy import DevicePower
from repro.core.profiles import paper_profiles, transformer_profile
from repro.core.reward import RewardWeights


def make_paper_env(weights: RewardWeights = RewardWeights(),
                   n_uavs: int = 3,
                   **env_kw) -> Tuple[EnvConfig, ProfileTables]:
    """The paper's testbed (3 UAVs); ``n_uavs`` scales the fleet — model
    assignment cycles through {vgg, resnet, densenet} like env_reset."""
    profs = paper_profiles()
    tables = build_tables([profs["vgg"], profs["resnet"], profs["densenet"]])
    cfg = EnvConfig(n_uavs=n_uavs, weights=weights.normalized(), **env_kw)
    return cfg, tables


# TPU v5e submesh regime: "device" = small head submesh (8 chips),
# "server" = shared tail submesh (64 chips, queued), link = ICI.
_TPU_LATENCY = LatencyParams(
    device_flops=8 * 197e12 * 0.4,      # 8 chips at 40% MFU
    server_flops=64 * 197e12 * 0.4,
    job_service_s=0.01,
    bw_min_bps=8 * 50e9 * 8 * 0.25,     # congested ICI share
    bw_max_bps=8 * 50e9 * 8,            # 8 links x 50 GB/s
)
_TPU_POWER = DevicePower(
    p_forward=0.0, p_vertical=0.0, p_rotate=0.0, p_hover=0.0,   # no kinetics
    p_compute=8 * 200.0,                # ~200 W per v5e chip
    p_tx_min=5.0, p_tx_max=20.0,        # ICI/DCN interface power proxy
    battery_wh=1e9,                     # pods don't run on batteries
)


def make_tpu_env(arch_names: Sequence[str],
                 weights: RewardWeights = RewardWeights(),
                 seq_len: int = 2048,
                 reduced: bool = False,
                 **env_kw) -> Tuple[EnvConfig, ProfileTables]:
    """TPU-adapted env whose version axis is the repro.quant registry
    (bf16 / w8 / w4 — see DESIGN.md §3). Each device's model is an arch
    name or a ModelConfig (a registered arch cut to what one chip serves).
    ``reduced=True`` profiles the smoke-test variant of each arch so table
    indices line up with an executable SplitServingEngine model (used by
    tests/examples that run the controller's decisions end-to-end)."""
    from repro.configs import ModelConfig, get_config

    cfgs = [a if isinstance(a, ModelConfig) else get_config(a)
            for a in arch_names]
    if reduced:
        cfgs = [c.reduced() for c in cfgs]
    profs = [transformer_profile(c, seq_len=seq_len) for c in cfgs]
    tables = build_tables(profs)
    # weight shipping: a (version, cut) switch stages the tail weights on
    # the server submesh; amortize over ~1/3 episode of request slots.
    env_kw.setdefault("weight_ship_slots", 32.0)
    cfg = EnvConfig(n_uavs=len(arch_names), latency=_TPU_LATENCY,
                    power=_TPU_POWER, weights=weights.normalized(),
                    frames_per_slot=1000.0,   # request batches per slot
                    **env_kw)
    return cfg, tables


def resolve_selection(model_cfg, profile, j: int, k: int):
    """Map a table action (version j, cut index k) to something the
    SplitServingEngine can execute: (quant version name, partition cut).

    ``profile`` must be the ModelProfile the tables were built from (same
    cfg), so the cut index addresses the same candidate list. Indices
    beyond this model's version/cut count clamp to the last entry — the
    same padding rule build_tables applies when mixing models of
    different sizes, so the executed action is the one the tables
    scored."""
    from repro.core import partition

    v = profile.versions[min(j, len(profile.versions) - 1)]
    layer = v.cut_points[min(k, len(v.cut_points) - 1)]
    return v.version, partition.cut_for_layer(model_cfg, layer)


def make_task_sampler(cfg: EnvConfig, trace, seed: int):
    """Adapt a workload trace (repro.sim.traces.Trace) into the
    ``task_sampler(episode) -> (episode_len, n_uavs)`` hook the batched
    trainers consume: per-slot offered load counts / (slot * peak_rps),
    the same normalization the fleet simulator feeds ``measured_state``,
    so the agent learns what bursts look like before it meets them
    online. Shared by the A2C and PPO training paths; requires
    cfg.peak_rps > 0 to normalize counts into the load feature."""
    if trace is None:
        return None
    if cfg.peak_rps <= 0:
        raise ValueError("trace-driven training needs cfg.peak_rps > 0 "
                         "to normalize counts into the load feature")

    def task_sampler(episode):
        rng = np.random.default_rng(
            np.random.SeedSequence([seed, episode]))
        gen = trace.stream(rng, cfg.n_uavs, cfg.slot_seconds)
        rows = [next(gen) for _ in range(cfg.episode_len)]
        return np.clip(np.asarray(rows, dtype=np.float32)
                       / (cfg.slot_seconds * cfg.peak_rps), 0.0, 1.0)

    return task_sampler


def train_agent(cfg: EnvConfig, tables: ProfileTables,
                ac: A2C.A2CConfig = A2C.A2CConfig(), seed: int = 0,
                log_every: int = 0, trace=None):
    """Train the A2C controller. ``ac.batch_envs = E`` rolls E vmapped
    env instances per update inside one jit (each with its own reset
    draw and, under a trace, its own sampled load sequence) — the same
    wall-clock per update buys E× the episodes and scenario diversity.
    ``trace`` (a repro.sim.traces.Trace) switches the episode's task
    feature from the Bernoulli draw to trace-driven offered load — see
    ``make_task_sampler``. For battery-drain parity with the per-request
    fleet metering, set cfg.frames_per_slot = slot_seconds * peak_rps
    (one frame per request at saturation)."""
    return A2C.train(cfg, tables, ac, jax.random.key(seed),
                     log_every=log_every,
                     task_sampler=make_task_sampler(cfg, trace, seed))


def decide(params, cfg: EnvConfig, tables: ProfileTables, state):
    """Greedy execution-profile decision for the current state."""
    obs = observe(cfg, tables, state).reshape(-1)
    valid = tables.version_valid[state["model_id"]]
    return A2C.greedy_actions(params, obs, valid)


def measured_state(cfg: EnvConfig, tables: ProfileTables, *,
                   battery_j, bandwidth, p_tx, queue_jobs, load,
                   model_id=None, activity=None, t: int = 0) -> Dict:
    """Assemble the env-state dict ``observe``/``decide`` consume from
    quantities a fleet actually measures online: remaining battery (J),
    link bandwidth (bps), transmit power (W), server queue depth (jobs)
    and per-device offered load in [0, 1] (observed arrival rate over a
    nominal capacity — Eq. 6's task-availability alpha generalized to a
    measured utilization). This is how the trace-driven simulator
    (repro.sim.fleet) runs the trained controller online each decision
    epoch: no env rollout, just measurements in, (version, cut) out."""
    battery_j = jnp.asarray(battery_j, jnp.float32)
    n = battery_j.shape[0]
    if model_id is None:
        model_id = jnp.arange(n, dtype=jnp.int32) % tables.n_models
    if activity is None:
        activity = jnp.tile(jnp.asarray(cfg.activity, jnp.float32)[None],
                            (n, 1))
    return {
        "battery_j": battery_j,
        "task": jnp.clip(jnp.asarray(load, jnp.float32), 0.0, 1.0),
        "p_tx": jnp.asarray(p_tx, jnp.float32),
        "model_id": jnp.asarray(model_id, jnp.int32),
        "activity": jnp.asarray(activity, jnp.float32),
        "bandwidth": jnp.asarray(bandwidth, jnp.float32),
        # cluster mode measures one depth per server ((S,)); the classic
        # scalar path is kept exactly as-is for bit-stable decides
        "queue": (jnp.asarray(queue_jobs, jnp.float32)
                  if np.ndim(queue_jobs) else jnp.float32(queue_jobs)),
        "t": jnp.int32(t),
    }


def evaluate_policy(cfg: EnvConfig, tables: ProfileTables,
                    policy, rng, episodes: int = 5) -> Dict:
    """Roll a policy (a ``repro.policies.Policy`` — anything exposing
    ``act(state, rng) -> (n, 2) int32`` built against this env); aggregate
    the paper's reported metrics + the (version, cut) selection histogram
    (Table II reproduction).

    Each episode is one jitted lax.scan over the slots — no host
    round-trip per slot — with the selection histogram built by a
    scatter-add over the (model, version, cut) indices. The per-episode
    rng threading (split per episode, split per slot, policy/env
    fold-ins) matches the historical per-slot Python loop, so fixed-seed
    results are unchanged up to float summation order."""
    if policy.env_cfg is not cfg or policy.tables is not tables:
        raise ValueError(
            f"policy {policy.name!r} was built against a different "
            "(env_cfg, tables) world than the one being evaluated; "
            "build it from the same objects")
    M, V, K = tables.n_models, tables.n_versions, tables.n_cuts

    @jax.jit
    def one_episode(rng):
        rng, k0 = jax.random.split(rng)
        state0 = env_reset(cfg, tables, k0)

        def step(carry, _):
            state, rng = carry
            rng, k = jax.random.split(rng)
            actions = policy.act(state, jax.random.fold_in(k, 7))
            state2, r, info = env_step(cfg, tables, state, actions,
                                       jax.random.fold_in(k, 13))
            out = {
                "actions": actions, "model_id": state["model_id"],
                "alive": info["alive"], "reward": r,
                "latency": jnp.mean(info["t_total"]),
                "energy": jnp.mean(info["e_infer"]),
                "acc_score": jnp.mean(info["acc_s"]),
                "lat_score": jnp.mean(info["lat_s"]),
                "en_score": jnp.mean(info["en_s"]),
                "alive_slots": jnp.sum(info["alive"]),
            }
            return (state2, rng), out

        (_, rng), tr = jax.lax.scan(step, (state0, rng), None,
                                    length=cfg.episode_len)
        m = tr.pop("model_id").reshape(-1)
        a = tr.pop("actions").reshape(-1, cfg.action_dim)
        alive = tr.pop("alive").reshape(-1)
        hist = jnp.zeros((M, V, K)).at[m, a[:, 0], a[:, 1]].add(alive)
        return rng, hist, {k: jnp.sum(v) for k, v in tr.items()}

    hist = np.zeros((M, V, K))
    agg = {k: 0.0 for k in ("reward", "latency", "energy", "acc_score",
                            "lat_score", "en_score", "alive_slots")}
    for ep in range(episodes):
        rng, ep_hist, sums = one_episode(rng)
        hist += np.asarray(ep_hist)
        for k in agg:
            agg[k] += float(sums[k])
    steps = episodes * cfg.episode_len
    out = {k: v / steps for k, v in agg.items()}
    out["selection_hist"] = hist
    # modal (version, cut index) per model — Table II analogue
    modal = {}
    for mi, name in enumerate(tables.names):
        if hist[mi].sum() > 0:
            j, c = np.unravel_index(np.argmax(hist[mi]), hist[mi].shape)
            modal[name] = (int(j), int(c))
    out["modal_selection"] = modal
    return out
