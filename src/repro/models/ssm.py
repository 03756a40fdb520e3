"""Mamba-1 selective SSM block (falcon-mamba).

Forward (train/prefill) uses a chunked scan: an outer ``lax.scan`` over
sequence chunks carries the (B, d_inner, d_state) recurrent state, and a
short inner scan runs the recurrence within each chunk — the discretized
(B, S, d_inner, d_state) tensor is never materialized for the full
sequence. Decode is a single recurrent step against {conv, ssm} state.
The Pallas kernel (kernels/mamba_scan.py) implements the same chunked
recurrence with VMEM tiling; kernels/ref.py oracles against this module.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.params import P
from repro.models.layers import causal_conv1d, causal_conv1d_step

SSM_CHUNK = 256


def plan_ssm(cfg: ModelConfig):
    d, di = cfg.d_model, cfg.d_inner
    n, r, k = cfg.ssm_state, cfg.resolved_dt_rank, cfg.ssm_conv

    def a_log_init(key, shape, dtype):
        # S4D-real init: A_n = -(n+1); stacking-aware (state dim is last)
        a = jnp.broadcast_to(
            jnp.arange(1, shape[-1] + 1, dtype=jnp.float32), shape)
        return jnp.log(a).astype(dtype)

    return {
        "in_proj": P((d, 2 * di), ("embed", "inner")),
        "conv_w": P((k, di), (None, "inner"), "normal", scale=0.1),
        "conv_b": P((di,), ("inner",), "zeros"),
        "x_proj": P((di, r + 2 * n), ("inner", None)),
        "dt_proj": P((r, di), (None, "inner"), scale=r ** -0.5),
        "dt_bias": P((di,), ("inner",),
                     lambda key, shape, dtype: jnp.full(shape, -4.6, dtype)),
        "a_log": P((di, n), ("inner", None), a_log_init, dtype="float32"),
        "d_skip": P((di,), ("inner",), "ones", dtype="float32"),
        "out_proj": P((di, d), ("inner", "embed")),
    }


def _ssm_params(cfg: ModelConfig, p, u):
    """u: (B, T, di) post-conv activations -> (dt, Bm, Cm)."""
    n, r = cfg.ssm_state, cfg.resolved_dt_rank
    xdbc = u @ p["x_proj"]                                  # (B,T,r+2n)
    dt = jax.nn.softplus(xdbc[..., :r] @ p["dt_proj"]
                         + p["dt_bias"]).astype(jnp.float32)  # (B,T,di)
    Bm = xdbc[..., r:r + n].astype(jnp.float32)             # (B,T,n)
    Cm = xdbc[..., r + n:].astype(jnp.float32)              # (B,T,n)
    return dt, Bm, Cm


def ssm_scan_chunked(cfg: ModelConfig, p, u, h0: Optional[jax.Array] = None,
                     chunk: int = SSM_CHUNK):
    """Selective scan. u: (B, S, di). Returns (y, h_final)."""
    B, S, di = u.shape
    n = cfg.ssm_state
    A = -jnp.exp(p["a_log"].astype(jnp.float32))            # (di, n)
    dt, Bm, Cm = _ssm_params(cfg, p, u)
    uf = u.astype(jnp.float32)

    h = h0 if h0 is not None else jnp.zeros((B, di, n), jnp.float32)
    chunk = min(chunk, S)
    if S % chunk != 0:
        chunk = S
    nc = S // chunk

    def step(h, xs):
        u_t, dt_t, B_t, C_t = xs                            # (B,di),(B,di),(B,n),(B,n)
        dA = jnp.exp(dt_t[..., None] * A[None])             # (B,di,n)
        dBu = (dt_t * u_t)[..., None] * B_t[:, None, :]     # (B,di,n)
        h = dA * h + dBu
        y = jnp.einsum("bdn,bn->bd", h, C_t)
        return h, y

    def chunk_body(h, xs):
        uc, dtc, Bc, Cc = xs                                # (B,chunk,·)
        h, yc = jax.lax.scan(
            step, h, (uc.transpose(1, 0, 2), dtc.transpose(1, 0, 2),
                      Bc.transpose(1, 0, 2), Cc.transpose(1, 0, 2)))
        return h, yc.transpose(1, 0, 2)                     # (B,chunk,di)

    if nc == 1:
        h, y = chunk_body(h, (uf, dt, Bm, Cm))
    else:
        split = lambda x: x.reshape(B, nc, chunk, x.shape[-1]).transpose(1, 0, 2, 3)
        h, ys = jax.lax.scan(chunk_body, h, (split(uf), split(dt),
                                             split(Bm), split(Cm)))
        y = ys.transpose(1, 0, 2, 3).reshape(B, S, di)
    y = y + uf * p["d_skip"][None, None]
    return y.astype(u.dtype), h


def apply_ssm(cfg: ModelConfig, p, x, *, mode: str, cache=None):
    """Mamba mixer. x: (B, S, d). Returns (out, new_cache).

    cache = {"conv": (B, K-1, di), "ssm": (B, di, n)} for decode.
    """
    B, S, _ = x.shape
    di = cfg.d_inner
    xz = x @ p["in_proj"]
    xin, z = xz[..., :di], xz[..., di:]

    new_cache = None
    if mode == "decode":
        u_t, conv_state = causal_conv1d_step(
            xin[:, 0], cache["conv"], p["conv_w"], p["conv_b"])
        u = jax.nn.silu(u_t)[:, None]                       # (B,1,di)
        y, h = ssm_scan_chunked(cfg, p, u, h0=cache["ssm"].astype(jnp.float32))
        new_cache = {"conv": conv_state, "ssm": h.astype(cache["ssm"].dtype)}
    else:
        from repro.kernels import ops as kops
        u = jax.nn.silu(causal_conv1d(xin, p["conv_w"], p["conv_b"]))
        if kops.use_pallas():
            dt, Bm, Cm = _ssm_params(cfg, p, u)
            y, h = kops.mamba_scan_full(cfg, p, u, dt, Bm, Cm)
        else:
            y, h = ssm_scan_chunked(cfg, p, u)
        if mode == "prefill":
            K = cfg.ssm_conv
            tail = xin[:, -(K - 1):]
            pad = jnp.zeros((B, max(0, (K - 1) - S), di), xin.dtype)
            new_cache = {"conv": jnp.concatenate([pad, tail], axis=1),
                         "ssm": h.astype(x.dtype)}
    out = (y * jax.nn.silu(z)) @ p["out_proj"]
    return out, new_cache
