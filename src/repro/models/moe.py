"""Mixture-of-Experts layer: top-k routing over all experts, a dropless
grouped matmul over the experts this chip holds, shared experts, and the
load-balance aux loss.

Expert parallelism: the layer holds experts ``0 .. experts_held - 1`` of
``n_experts`` (all of them by default). It routes every token over all
``n_experts`` and returns its own experts' part of the result; the other
chips' parts, and the exchange that would sum them, are not here. With
every expert held that part is the whole layer.

Dropless: every (token, slot) routed to a held expert is computed. The
rows are sorted by expert into a buffer sized for the worst case (every
token's slots on held experts), each expert's rows starting on a row tile
of ``kernels/grouped_matmul.py``, and the three expert matmuls run as
grouped matmuls over it (the Pallas kernel on a TPU, its oracle
elsewhere), so no token is dropped and no expert computes rows it was
not routed.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels.grouped_matmul import padded_starts, rows_bound
from repro.models.params import P
from repro.models.layers import plan_mlp, apply_mlp


def plan_moe(cfg: ModelConfig):
    d, f, E = cfg.d_model, cfg.moe_d_ff, cfg.n_experts
    held = cfg.resolved_experts_held
    plan = {
        "router": P((d, E), ("embed", "experts"), scale=d ** -0.5),
        "w_gate": P((held, d, f), ("experts", "embed", "ff")),
        "w_up": P((held, d, f), ("experts", "embed", "ff")),
        "w_down": P((held, f, d), ("experts", "ff", "embed")),
    }
    if cfg.n_shared_experts:
        plan["shared"] = plan_mlp(cfg, d_ff=cfg.moe_d_ff * cfg.n_shared_experts)
    return plan


def route(cfg: ModelConfig, p, x):
    """Top-k routing over all experts. x: (T, d). Returns (weights (T, K)
    normalized over the token's top k, experts (T, K), aux loss)."""
    E, K = cfg.n_experts, cfg.top_k
    # full float32 products: the top-k choice flips on rounding near ties
    logits = jnp.matmul(x.astype(jnp.float32),
                        p["router"].astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)                   # (T,E)
    top_p, top_e = jax.lax.top_k(probs, K)                    # (T,K)
    top_p = top_p / jnp.maximum(jnp.sum(top_p, -1, keepdims=True), 1e-9)
    # load-balance loss terms (Switch-style): mean prob * mean assignment
    me = jnp.mean(probs, axis=0)                              # (E,)
    ce = jnp.mean(jnp.sum(jax.nn.one_hot(top_e, E, dtype=jnp.float32),
                          axis=1), axis=0)                    # (E,)
    aux = E * jnp.sum(me * ce) / K
    return top_p, top_e, aux


def dispatch(cfg: ModelConfig, top_e):
    """Where each (token, slot) of the held experts goes. Returns (rows
    (T, K): its row in the expert buffer, or the buffer's size where its
    expert is not held; token (R,): the token of each buffer row, or T for
    padding; starts (held + 1,): each expert's first row). A slot's place
    in its expert's rows is the count of earlier slots routed there (a
    counting sort: no sort of the T K slots)."""
    T, K = top_e.shape
    held = cfg.resolved_experts_held
    R = rows_bound(T * min(K, held), held)
    e = top_e.reshape(-1)
    key = jnp.where(e < held, e, held)                 # unheld: group held
    onehot = jax.nn.one_hot(key, held + 1, dtype=jnp.int32)  # (T K, held+1)
    before = jnp.cumsum(onehot, axis=0) - onehot
    rank = jnp.take_along_axis(before, key[:, None], axis=1)[:, 0]
    starts = padded_starts(jnp.sum(onehot[:, :held], axis=0))
    rows = jnp.where(key < held, starts[jnp.minimum(key, held - 1)] + rank,
                     R)
    token = jnp.full((R,), T, jnp.int32).at[rows].set(
        jnp.arange(T * K, dtype=jnp.int32) // K, mode="drop")
    return rows.reshape(T, K), token, starts


def held_experts(cfg: ModelConfig, p, x, top_p, top_e):
    """The held experts' part of the layer, dropless. x: (T, d)."""
    from repro.kernels import ops

    T, d = x.shape
    rows, token, starts = dispatch(cfg, top_e)
    xr = jnp.concatenate([x, jnp.zeros((1, d), x.dtype)])[token]
    g = ops.grouped_matmul(xr, p["w_gate"].astype(x.dtype), starts)
    u = ops.grouped_matmul(xr, p["w_up"].astype(x.dtype), starts)
    y = ops.grouped_matmul(jax.nn.silu(g) * u,
                           p["w_down"].astype(x.dtype), starts)
    # unheld slots read the zero row past the buffer
    y = jnp.concatenate([y, jnp.zeros((1, d), y.dtype)])
    return jnp.einsum("tk,tkd->td", top_p.astype(y.dtype), y[rows])


def apply_moe(cfg: ModelConfig, p, x):
    """x: (B, S, d) -> (y, aux_loss): the held experts' part of the routed
    experts, plus the shared experts."""
    B, S, d = x.shape
    xf = x.reshape(B * S, d)
    top_p, top_e, aux = route(cfg, p, xf)
    y = held_experts(cfg, p, xf, top_p, top_e).reshape(B, S, d)
    if cfg.n_shared_experts:
        y = y + apply_mlp(cfg, p["shared"], x)
    return y.astype(x.dtype), aux * cfg.router_aux_weight
