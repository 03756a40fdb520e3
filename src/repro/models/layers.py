"""Shared primitive layers: norms, MLPs, rotary embeddings, positions."""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.params import P
from repro.quant.quantize import QTensor


def dense(x, w):
    """``x @ w`` where w may be a quantized ``QTensor`` leaf.

    The quantized path dispatches through kernels/ops.py (which runs the
    Pallas int8 kernel on a TPU backend); the import is deferred because
    kernels -> ref -> ssm imports this module at package-init time.
    """
    if isinstance(w, QTensor):
        from repro.kernels import ops
        return ops.quantized_dense(x, w)
    return x @ w


# --------------------------------------------------------------------------
# norms
# --------------------------------------------------------------------------

def plan_norm(cfg: ModelConfig, dim: Optional[int] = None):
    d = dim if dim is not None else cfg.d_model
    if cfg.norm == "layernorm":
        return {"scale": P((d,), (None,), "ones"),
                "bias": P((d,), (None,), "zeros")}
    return {"scale": P((d,), (None,), "ones")}


def apply_norm(cfg: ModelConfig, p, x, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    if "bias" in p:  # layernorm
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps)
        y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    else:  # rmsnorm
        ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(ms + eps) * p["scale"].astype(jnp.float32)
    return y.astype(x.dtype)


def rms_norm_headwise(x, scale, eps: float = 1e-6):
    """Per-head RMSNorm on the trailing head_dim (qwen3 qk_norm)."""
    xf = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(ms + eps) * scale.astype(jnp.float32)).astype(x.dtype)


# --------------------------------------------------------------------------
# MLP
# --------------------------------------------------------------------------

def plan_mlp(cfg: ModelConfig, d_in: Optional[int] = None,
             d_ff: Optional[int] = None, bias: bool = False):
    d = d_in if d_in is not None else cfg.d_model
    f = d_ff if d_ff is not None else cfg.d_ff
    plan = {"w_down": P((f, d), ("ff", "embed"))}
    if cfg.mlp_act in ("swiglu", "geglu"):
        plan["w_gate"] = P((d, f), ("embed", "ff"))
        plan["w_up"] = P((d, f), ("embed", "ff"))
    else:  # gelu
        plan["w_up"] = P((d, f), ("embed", "ff"))
    if bias:
        plan["b_up"] = P((f,), ("ff",), "zeros")
        plan["b_down"] = P((d,), (None,), "zeros")
    return plan


def apply_mlp(cfg: ModelConfig, p, x):
    if "w_gate" in p:
        g = dense(x, p["w_gate"])
        u = dense(x, p["w_up"])
        act = jax.nn.silu if cfg.mlp_act == "swiglu" else jax.nn.gelu
        h = act(g) * u
    else:
        h = dense(x, p["w_up"])
        if "b_up" in p:
            h = h + p["b_up"]
        h = jax.nn.gelu(h)
    y = dense(h, p["w_down"])
    if "b_down" in p:
        y = y + p["b_down"]
    return y


# --------------------------------------------------------------------------
# positions
# --------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float):
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))


def yarn_freqs(head_dim: int, theta: float, yarn):
    """YaRN inverse frequencies (Hugging Face ``_compute_yarn_parameters``,
    truncated correction range): frequency j keeps its value below the
    correction range of ``beta_fast`` .. ``beta_slow`` rotations over the
    original context, is divided by ``factor`` above it, and ramps
    linearly in between."""
    def corr(rotations):
        return (head_dim * math.log(yarn.original_max_position
                                    / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    lo = max(math.floor(corr(yarn.beta_fast)), 0)
    hi = min(math.ceil(corr(yarn.beta_slow)), head_dim - 1)
    inv = rope_freqs(head_dim, theta)
    j = jnp.arange(head_dim // 2, dtype=jnp.float32)
    ramp = jnp.clip((j - lo) / max(hi - lo, 1e-3), 0.0, 1.0)
    return inv / yarn.factor * ramp + inv * (1.0 - ramp)


def apply_rope(x, positions, theta: float, yarn=None):
    """x: (..., S, H, D); positions: (S,) int32. ``yarn`` (a
    ``configs.base.RopeYarn``) rescales the frequencies and multiplies
    cos and sin by its attention factor."""
    half = x.shape[-1] // 2
    inv = (rope_freqs(x.shape[-1], theta) if yarn is None
           else yarn_freqs(x.shape[-1], theta, yarn))         # (half,)
    ang = positions.astype(jnp.float32)[:, None] * inv[None]  # (S, half)
    cos = jnp.cos(ang)[..., None, :]                          # (S, 1, half)
    sin = jnp.sin(ang)[..., None, :]
    if yarn is not None:
        cos, sin = cos * yarn.attention_factor, sin * yarn.attention_factor
    x1, x2 = x[..., :half], x[..., half:]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out = jnp.concatenate([xf1 * cos - xf2 * sin,
                           xf2 * cos + xf1 * sin], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_positions(n: int, d: int, offset=0):
    pos = (jnp.arange(n, dtype=jnp.float32) + offset)[:, None]
    half = d // 2
    freq = jnp.exp(-jnp.log(10_000.0) * jnp.arange(half, dtype=jnp.float32) / max(half - 1, 1))
    ang = pos * freq[None]
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)  # (n, d)


# --------------------------------------------------------------------------
# causal depthwise conv (mamba / rg-lru), as shifted adds (SPMD friendly)
# --------------------------------------------------------------------------

def causal_conv1d(x, w, b=None):
    """x: (B, S, C); w: (K, C) depthwise causal kernel; returns (B, S, C)."""
    K = w.shape[0]
    y = x * w[K - 1]
    for i in range(1, K):
        shifted = jnp.pad(x, ((0, 0), (i, 0), (0, 0)))[:, : x.shape[1]]
        y = y + shifted * w[K - 1 - i]
    if b is not None:
        y = y + b
    return y


def causal_conv1d_step(x_t, conv_state, w, b=None):
    """One decode step. x_t: (B, C); conv_state: (B, K-1, C) holding the
    previous K-1 inputs (oldest first). Returns (y_t, new_conv_state)."""
    K = w.shape[0]
    window = jnp.concatenate([conv_state, x_t[:, None, :]], axis=1)  # (B, K, C)
    y = jnp.einsum("bkc,kc->bc", window.astype(jnp.float32),
                   w.astype(jnp.float32)).astype(x_t.dtype)
    if b is not None:
        y = y + b
    return y, window[:, 1:]
