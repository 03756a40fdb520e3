"""RG-LRU recurrent block (Griffin / RecurrentGemma).

Recurrent block: two branches from the input — (i) linear -> GeLU gate,
(ii) linear -> causal conv1d -> RG-LRU — merged multiplicatively and
projected back. RG-LRU recurrence (Griffin eqs. 1-4):

    r_t = sigmoid(W_a x_t + b_a)          recurrence gate
    i_t = sigmoid(W_x x_t + b_x)          input gate
    log a_t = -c * softplus(Lambda) * r_t  (a = diag, c = 8)
    h_t = a_t * h_{t-1} + sqrt(1 - a_t^2) * (i_t * x_t)

Train/prefill uses ``lax.associative_scan`` over the diagonal linear
recurrence; decode is one step. kernels/rglru_scan.py is the Pallas twin.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.params import P
from repro.models.layers import causal_conv1d, causal_conv1d_step

LRU_C = 8.0


def plan_rec(cfg: ModelConfig):
    d = cfg.d_model
    w = cfg.resolved_lru_width
    k = cfg.ssm_conv

    def lam_init(key, shape, dtype):
        # a ~ U[0.9, 0.999]: Lambda = softplus^-1(-log a / c)
        u = jax.random.uniform(key, shape, jnp.float32, 0.9, 0.999)
        t = -jnp.log(u) / LRU_C
        return jnp.log(jnp.expm1(jnp.maximum(t, 1e-8))).astype(dtype)

    return {
        "w_gate_branch": P((d, w), ("embed", "lru")),
        "w_rec_branch": P((d, w), ("embed", "lru")),
        "conv_w": P((k, w), (None, "lru"), "normal", scale=0.1),
        "conv_b": P((w,), ("lru",), "zeros"),
        "w_a": P((w, w), ("lru", None), scale=w ** -0.5),
        "b_a": P((w,), (None,), "zeros"),
        "w_x": P((w, w), ("lru", None), scale=w ** -0.5),
        "b_x": P((w,), (None,), "zeros"),
        "lam": P((w,), (None,), lam_init, dtype="float32"),
        "w_out": P((w, d), ("lru", "embed")),
    }


def _gates(p, u):
    r = jax.nn.sigmoid(u @ p["w_a"] + p["b_a"]).astype(jnp.float32)
    i = jax.nn.sigmoid(u @ p["w_x"] + p["b_x"]).astype(jnp.float32)
    log_a = -LRU_C * jax.nn.softplus(p["lam"].astype(jnp.float32)) * r
    a = jnp.exp(log_a)
    gx = jnp.sqrt(jnp.maximum(1.0 - a * a, 1e-12)) * (i * u.astype(jnp.float32))
    return a, gx


def rglru_scan(p, u, h0: Optional[jax.Array] = None):
    """u: (B, S, w). Diagonal linear recurrence via associative_scan."""
    B, S, w = u.shape
    a, gx = _gates(p, u)                                    # (B,S,w) each
    if h0 is not None:
        # fold initial state into the first element
        gx = gx.at[:, 0].add(a[:, 0] * h0.astype(jnp.float32))

    def combine(c1, c2):
        a1, b1 = c1
        a2, b2 = c2
        return a2 * a1, a2 * b1 + b2

    _, h = jax.lax.associative_scan(combine, (a, gx), axis=1)
    return h.astype(u.dtype), h[:, -1]


def apply_rec(cfg: ModelConfig, p, x, *, mode: str, cache=None):
    """Griffin recurrent mixer. Returns (out, new_cache).

    cache = {"conv": (B, K-1, w), "lru": (B, w)}.
    """
    B, S, _ = x.shape
    w = cfg.resolved_lru_width
    gate = jax.nn.gelu(x @ p["w_gate_branch"])
    u = x @ p["w_rec_branch"]

    new_cache = None
    if mode == "decode":
        u_t, conv_state = causal_conv1d_step(
            u[:, 0], cache["conv"], p["conv_w"], p["conv_b"])
        a, gx = _gates(p, u_t[:, None])
        h = a[:, 0] * cache["lru"].astype(jnp.float32) + gx[:, 0]
        y = h[:, None].astype(x.dtype)
        new_cache = {"conv": conv_state, "lru": h.astype(cache["lru"].dtype)}
    else:
        from repro.kernels import ops as kops
        uc = causal_conv1d(u, p["conv_w"], p["conv_b"])
        if kops.use_pallas():
            a, gx = _gates(p, uc)
            y32, h_last = kops.rglru_scan_full(a, gx)
            y = y32.astype(x.dtype)
        else:
            y, h_last = rglru_scan(p, uc)
        if mode == "prefill":
            K = cfg.ssm_conv
            tail = u[:, -(K - 1):]
            pad = jnp.zeros((B, max(0, (K - 1) - S), w), u.dtype)
            new_cache = {"conv": jnp.concatenate([pad, tail], axis=1),
                         "lru": h_last.astype(x.dtype)}
    return (y * gate) @ p["w_out"], new_cache
