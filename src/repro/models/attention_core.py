"""Attention math: plain masked attention + chunked (flash-style) attention.

Both are pure jnp; the chunked path keeps live score blocks at
(B, G*HK, q_chunk, kv_chunk) so 32k-token prefill lowers without
materializing (S, S) scores. These functions double as the oracle
reference for the Pallas flash-attention kernel (kernels/ref.py imports
``plain_attention``).

Conventions: q (B, Sq, H, Dh); k, v (B, Skv, HK, Dh) with H % HK == 0 (GQA).
positions are absolute token indices; masking is positional so ring-buffer
(sliding-window) caches work with the same code path.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _mask(qp, kp, *, causal: bool, window: Optional[int]):
    """qp: (Sq,), kp: (Skv,) absolute positions; kp < 0 marks invalid slots."""
    m = kp[None, :] >= 0
    if causal:
        m &= kp[None, :] <= qp[:, None]
    if window is not None:
        m &= (qp[:, None] - kp[None, :]) < window
    return m  # (Sq, Skv)


def plain_attention(q, k, v, *, q_positions, kv_positions, causal=True,
                    window=None, logit_scale=None):
    B, Sq, H, Dh = q.shape
    HK = k.shape[2]
    G = H // HK
    scale = logit_scale if logit_scale is not None else Dh ** -0.5
    qg = q.reshape(B, Sq, G, HK, Dh)
    scores = jnp.einsum("bqghd,bkhd->bghqk", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    mask = _mask(q_positions, kv_positions, causal=causal, window=window)
    scores = jnp.where(mask[None, None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bghqk,bkhd->bqghd", probs, v.astype(jnp.float32))
    return out.reshape(B, Sq, H, v.shape[-1]).astype(q.dtype)


def chunked_attention(q, k, v, *, q_positions, kv_positions, causal=True,
                      window=None, q_chunk=512, kv_chunk=1024,
                      logit_scale=None):
    """Flash-style online-softmax attention, scan over q and kv chunks."""
    B, Sq, H, Dh = q.shape
    Skv, HK = k.shape[1], k.shape[2]
    Dv = v.shape[-1]
    G = H // HK
    scale = logit_scale if logit_scale is not None else Dh ** -0.5

    q_chunk = min(q_chunk, Sq)
    kv_chunk = min(kv_chunk, Skv)
    # pad to multiples
    def pad_to(x, n, axis, value=0):
        pad = (-x.shape[axis]) % n
        if pad == 0:
            return x
        widths = [(0, 0)] * x.ndim
        widths[axis] = (0, pad)
        return jnp.pad(x, widths, constant_values=value)

    qp = pad_to(q_positions, q_chunk, 0, value=0)
    kp = pad_to(kv_positions, kv_chunk, 0, value=-1)   # padded kv = invalid
    q_ = pad_to(q, q_chunk, 1)
    k_ = pad_to(k, kv_chunk, 1)
    v_ = pad_to(v, kv_chunk, 1)
    NQ, NK = q_.shape[1] // q_chunk, k_.shape[1] // kv_chunk

    qb = q_.reshape(B, NQ, q_chunk, G, HK, Dh).astype(jnp.float32)
    kb = k_.reshape(B, NK, kv_chunk, HK, Dh).transpose(
        1, 0, 2, 3, 4).astype(jnp.float32)
    vb = v_.reshape(B, NK, kv_chunk, HK, Dv).transpose(
        1, 0, 2, 3, 4).astype(jnp.float32)
    qpb = qp.reshape(NQ, q_chunk)
    kpb = kp.reshape(NK, kv_chunk)

    def q_block(carry, qi):
        qcb, qpos = qi   # (B, qc, G, HK, Dh), (qc,)

        def kv_block(acc, ki):
            m_run, l_run, o_run = acc
            kcb, vcb, kpos = ki
            s = jnp.einsum("bqghd,bkhd->bghqk", qcb, kcb) * scale
            mask = _mask(qpos, kpos, causal=causal, window=window)
            s = jnp.where(mask[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m_run, jnp.max(s, axis=-1))
            alpha = jnp.exp(m_run - m_new)
            p = jnp.exp(s - m_new[..., None])
            l_new = l_run * alpha + jnp.sum(p, axis=-1)
            o_new = o_run * alpha[..., None] + jnp.einsum(
                "bghqk,bkhd->bghqd", p, vcb)
            return (m_new, l_new, o_new), None

        m0 = jnp.full((B, G, HK, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, G, HK, q_chunk), jnp.float32)
        o0 = jnp.zeros((B, G, HK, q_chunk, Dv), jnp.float32)
        (m, l, o), _ = jax.lax.scan(kv_block, (m0, l0, o0), (kb, vb, kpb))
        out = o / jnp.maximum(l, 1e-30)[..., None]          # (B,G,HK,qc,Dh)
        return carry, out.transpose(0, 3, 1, 2, 4)          # (B,qc,G,HK,Dh)

    _, outs = jax.lax.scan(q_block, None,
                           (qb.transpose(1, 0, 2, 3, 4, 5), qpb))
    out = outs.transpose(1, 0, 2, 3, 4, 5).reshape(B, NQ * q_chunk, H, Dv)
    return out[:, :Sq].astype(q.dtype)


def chunked_attention_causal_skip(q, k, v, *, q_positions, kv_positions,
                                  window=None, logit_scale=None,
                                  q_chunk=512, kv_chunk=1024):
    """Causal chunked attention that only COMPUTES the kv prefix each q
    block can see (python loop over q blocks, static prefix slices) —
    halves attention FLOPs vs the masked-full scan at the cost of a
    larger HLO (NQ distinct block programs). Perf-iteration variant."""
    B, Sq, H, Dh = q.shape
    assert Sq == k.shape[1], "causal_skip assumes aligned self-attention"
    q_chunk = min(q_chunk, Sq)
    nq = -(-Sq // q_chunk)
    outs = []
    for i in range(nq):
        lo, hi = i * q_chunk, min((i + 1) * q_chunk, Sq)
        kv_hi = hi  # causal: block i sees keys < hi
        outs.append(chunked_attention(
            q[:, lo:hi], k[:, :kv_hi], v[:, :kv_hi],
            q_positions=q_positions[lo:hi], kv_positions=kv_positions[:kv_hi],
            causal=True, window=window, logit_scale=logit_scale,
            q_chunk=q_chunk, kv_chunk=kv_chunk))
    return jnp.concatenate(outs, axis=1)


def attention(q, k, v, *, q_positions, kv_positions, causal=True, window=None,
              logit_scale=None, chunked_threshold=2048,
              q_chunk=512, kv_chunk=1024, causal_skip=False):
    """Dispatch: Pallas flash kernel (``kops.use_pallas()``), else chunked
    for long sequences, else plain."""
    from repro.kernels import ops as kops
    if kops.use_pallas() and q.shape[1] == k.shape[1]:
        out = kops.attention_bhsd(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), causal=causal, window=window,
            logit_scale=logit_scale)
        return out.transpose(0, 2, 1, 3)
    if (causal_skip and causal and q.shape[1] == k.shape[1]
            and q.shape[1] > q_chunk):
        return chunked_attention_causal_skip(
            q, k, v, q_positions=q_positions, kv_positions=kv_positions,
            window=window, logit_scale=logit_scale, q_chunk=q_chunk,
            kv_chunk=kv_chunk)
    if q.shape[1] * k.shape[1] > chunked_threshold ** 2:
        return chunked_attention(q, k, v, q_positions=q_positions,
                                 kv_positions=kv_positions, causal=causal,
                                 window=window, logit_scale=logit_scale,
                                 q_chunk=q_chunk, kv_chunk=kv_chunk)
    return plain_attention(q, k, v, q_positions=q_positions,
                           kv_positions=kv_positions, causal=causal,
                           window=window, logit_scale=logit_scale)
