"""Flash-decode: single-token GQA attention over a ring KV cache, Pallas.

Decode attention is memory-bound (stream the whole cache per token); the
kernel tiles the cache sequence into VMEM blocks, carries the online-softmax
state in scratch, and applies the ring-buffer positional mask *inside* the
kernel (slot s holds absolute position pos - ((pos - s) mod C); slots with
negative positions or outside the sliding window are masked) — so the same
kernel serves full-cache decode_32k and windowed long_500k.

Layout: q (B, H, Dh); k, v (B, HK, C, Dh); pos scalar int32. The G = H/HK
query heads that share a kv head are one (G, Dh) block, so each cache block
is read once per kv head rather than once per query head.
grid = (B, HK, C/bk); the kv grid dim is sequential and accumulates.
Oracle: models/attention.py decode path (plain_attention over ring cache).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr,
                   acc_scr, *, scale: float, window: Optional[int],
                   bk: int, nk: int, cache_len: int):
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    pos = pos_ref[0]
    q = q_ref[0, 0].astype(jnp.float32)             # (G, Dh)
    k = k_ref[0, 0].astype(jnp.float32)             # (bk, Dh)
    v = v_ref[0, 0].astype(jnp.float32)             # (bk, Dv)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    # ring-buffer positional mask
    slots = ik * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    slot_pos = pos - jnp.mod(pos - slots, cache_len)
    mask = (slot_pos >= 0) & (slots < cache_len)
    if window is not None:
        mask &= (pos - slot_pos) < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, None])
    l_new = l_scr[...] * alpha + jnp.sum(p, axis=-1)
    acc_new = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    m_scr[...] = m_new
    l_scr[...] = l_new
    acc_scr[...] = acc_new

    @pl.when(ik == nk - 1)
    def _finalize():
        out = acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)[:, None]
        o_ref[0, 0] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "bk", "interpret",
                                             "logit_scale"))
def flash_decode(q, k, v, pos, *, window: Optional[int] = None,
                 logit_scale: Optional[float] = None, bk: int = 128,
                 interpret: bool = False):
    """q: (B, H, Dh); k, v: (B, HK, C, Dh) ring caches; pos: () int32.

    Returns (B, H, Dv). The current token must already be written at slot
    pos % C (matching models/attention.py decode semantics).
    """
    B, H, Dh = q.shape
    _, HK, C, Dv = v.shape
    assert H % HK == 0
    G = H // HK
    scale = logit_scale if logit_scale is not None else Dh ** -0.5
    bk = min(bk, C)

    def pad(x):
        p = (-x.shape[2]) % bk
        if p == 0:
            return x
        return jnp.pad(x, ((0, 0), (0, 0), (0, p), (0, 0)))

    k_, v_ = pad(k), pad(v)
    nk = k_.shape[2] // bk
    pos_arr = jnp.asarray(pos, jnp.int32).reshape(1)
    # model layout (G, HK): query head g * HK + h reads kv head h
    qg = q.reshape(B, G, HK, Dh).transpose(0, 2, 1, 3)     # (B, HK, G, Dh)

    kernel = functools.partial(_decode_kernel, scale=scale, window=window,
                               bk=bk, nk=nk, cache_len=C)
    out = pl.pallas_call(
        kernel,
        grid=(B, HK, nk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),   # pos scalar
            pl.BlockSpec((1, 1, G, Dh), lambda b, h, j: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, bk, Dh), lambda b, h, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, bk, Dv), lambda b, h, j: (b, h, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, Dv), lambda b, h, j: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, HK, G, Dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G, Dv), jnp.float32),
        ],
        interpret=interpret,
    )(pos_arr, qg, k_, v_)
    return out.transpose(0, 2, 1, 3).reshape(B, H, Dv)
