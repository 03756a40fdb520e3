"""Baseline execution-profile policies the paper implicitly compares
against: device-only, full-offload, random, and a per-step greedy oracle.

The greedy oracle enumerates every (version, cut) pair per UAV under the
*current* state and picks the per-UAV reward argmax — since Eq. 8 averages
a per-UAV score, per-UAV argmax is the per-step optimum (the RL agent can
only beat it through multi-step battery/queue effects). It scores the
full (V, K) grid through the single pricing core (``core.pricing``), so
it ranks actions under exactly the physics the env rewards and the fleet
simulator meters.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import pricing
from repro.core.env import EnvConfig, ProfileTables


def _with_server(cfg: EnvConfig, actions, srv=None):
    """Append a server column when the env runs in cluster mode; static
    baselines default to server 0 (the conventional primary target)."""
    if cfg.cluster is None:
        return actions
    n = actions.shape[0]
    if srv is None:
        srv = jnp.zeros((n,), jnp.int32)
    return jnp.concatenate([actions, srv[:, None].astype(jnp.int32)], -1)


def device_only(cfg: EnvConfig, tables: ProfileTables, state, rng=None):
    """Lightweight version, run everything locally (last cut)."""
    n = state["model_id"].shape[0]
    a = jnp.stack([jnp.zeros((n,), jnp.int32),
                   jnp.full((n,), tables.n_cuts - 1, jnp.int32)], -1)
    return _with_server(cfg, a)


def full_offload(cfg: EnvConfig, tables: ProfileTables, state, rng=None):
    """Heavy version, cut as early as possible."""
    n = state["model_id"].shape[0]
    j = (tables.version_valid[state["model_id"]].sum(-1) - 1).astype(jnp.int32)
    return _with_server(cfg, jnp.stack([j, jnp.zeros((n,), jnp.int32)], -1))


def random_policy(cfg: EnvConfig, tables: ProfileTables, state, rng):
    """Uniform over each device's *valid* versions and all cuts (and, in
    cluster mode, servers). Sampling randint(0, n_versions) % nv would
    bias toward low version indices whenever a model has fewer versions
    than the padded table width; randint takes a per-device maxval, so
    sample [0, nv) directly."""
    n = state["model_id"].shape[0]
    k1, k2, k3 = jax.random.split(rng, 3)
    nv = tables.version_valid[state["model_id"]].sum(-1).astype(jnp.int32)
    j = jax.random.randint(k1, (n,), 0, nv)
    k = jax.random.randint(k2, (n,), 0, tables.n_cuts)
    a = jnp.stack([j, k], -1).astype(jnp.int32)
    if cfg.cluster is None:
        return a
    srv = jax.random.randint(k3, (n,), 0, cfg.cluster.n_servers)
    return _with_server(cfg, a, srv)


def greedy_oracle(cfg: EnvConfig, tables: ProfileTables, state, rng=None):
    """Per-step per-UAV reward argmax over all (j, k) — and over the
    server axis too in cluster mode. Canonical registry name:
    ``greedy_oracle`` (repro.policies)."""
    n = state["model_id"].shape[0]
    V, K = tables.n_versions, tables.n_cuts
    S = 1 if cfg.cluster is None else cfg.cluster.n_servers
    w = cfg.weights
    view = pricing.view_from_state(state)

    if cfg.cluster is None:
        jj, kk = jnp.meshgrid(jnp.arange(V), jnp.arange(K), indexing="ij")
        cands = jnp.stack([jj.ravel(), kk.ravel()], -1)          # (VK, 2)
    else:
        jj, kk, ss = jnp.meshgrid(jnp.arange(V), jnp.arange(K),
                                  jnp.arange(S), indexing="ij")
        cands = jnp.stack([jj.ravel(), kk.ravel(), ss.ravel()], -1)
    cands = cands.astype(jnp.int32)                              # (VKS, A)

    def score(cand):
        actions = jnp.tile(cand[None], (n, 1))
        br = pricing.price_actions(cfg, tables, view, actions)
        valid = tables.version_valid[state["model_id"], cand[0]]
        s = (w.w_acc * br.acc_score + w.w_lat * br.lat_score
             + w.w_energy * br.energy_score + w.w_stab * br.stab_score)
        return jnp.where(valid > 0, s, -jnp.inf)

    scores = jax.vmap(score)(cands)          # (VKS, n)
    best = jnp.argmax(scores, axis=0)        # (n,)
    return cands[best]
