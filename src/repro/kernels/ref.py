"""Pure-jnp oracles for every Pallas kernel (allclose targets in tests)."""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from repro.models.attention_core import plain_attention
from repro.models.ssm import ssm_scan_chunked
from repro.models.rglru import rglru_scan


def flash_attention_ref(q, k, v, *, causal=True, window=None,
                        logit_scale=None):
    """q: (B,H,Sq,D); k,v: (B,HK,Skv,D) -> (B,H,Sq,Dv)  [kernel layout]."""
    out = plain_attention(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3),
        q_positions=jnp.arange(q.shape[2], dtype=jnp.int32),
        kv_positions=jnp.arange(k.shape[2], dtype=jnp.int32),
        causal=causal, window=window, logit_scale=logit_scale)
    return out.transpose(0, 2, 1, 3)


def grouped_matmul_ref(lhs, rhs, starts):
    """lhs (M, K) rows laid out by starts (G + 1,); rhs (G, K, N) -> (M, N):
    row r of group g times rhs[g]; rows past starts[G] give zeros."""
    G = rhs.shape[0]
    row = jnp.arange(lhs.shape[0], dtype=jnp.int32)
    group = jnp.searchsorted(starts[1:], row, side="right")
    out = jnp.zeros((lhs.shape[0], rhs.shape[2]), jnp.float32)
    for g in range(G):
        out = out + jnp.where((group == g)[:, None],
                              jnp.matmul(lhs, rhs[g],
                                         preferred_element_type=jnp.float32),
                              0.0)
    return out.astype(lhs.dtype)


def mamba_scan_ref(cfg, p, u, h0=None):
    """Chunked selective-scan oracle (models/ssm.py)."""
    return ssm_scan_chunked(cfg, p, u, h0=h0)


def rglru_scan_ref(a, gx, h0=None):
    """Diagonal linear recurrence oracle: h_t = a_t h_{t-1} + gx_t.

    a, gx: (B, S, W) f32. Returns (h_seq, h_last)."""
    import jax

    def combine(c1, c2):
        a1, b1 = c1
        a2, b2 = c2
        return a2 * a1, a2 * b1 + b2

    if h0 is not None:
        gx = gx.at[:, 0].add(a[:, 0] * h0)
    _, h = jax.lax.associative_scan(combine, (a, gx), axis=1)
    return h, h[:, -1]
