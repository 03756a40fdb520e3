"""Flash attention (forward) as a Pallas TPU kernel.

Online-softmax attention with explicit BlockSpec VMEM tiling:
grid = (B, H, num_q_blocks, num_kv_blocks); the innermost (kv) grid dim is
sequential ("arbitrary") and accumulates (m, l, acc) in VMEM scratch —
the canonical TPU flash pattern. GQA is handled in the k/v index_map
(query head h reads kv head h // group_size), so grouped keys/values are
never materialized. Causal + sliding-window masking is positional.

TPU is the TARGET; correctness is validated on CPU with interpret=True
against kernels/ref.py (pure jnp oracle). Block defaults (128) align with
the MXU's 128-lane systolic tiles.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, causal: bool, window: Optional[int],
                  bq: int, bk: int, nk: int, skv: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)          # (bq, D)
    k = k_ref[0, 0].astype(jnp.float32)          # (bk, D)
    v = v_ref[0, 0].astype(jnp.float32)          # (bk, Dv)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    qpos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = kpos < skv           # exclude zero-padded kv slots
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= (qpos - kpos) < window
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


@functools.partial(jax.jit, static_argnames=("causal", "window", "bq", "bk",
                                             "interpret", "logit_scale"))
def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None,
                    logit_scale: Optional[float] = None,
                    bq: int = 128, bk: int = 128,
                    interpret: bool = False):
    """q: (B, H, Sq, D); k, v: (B, HK, Skv, D). Returns (B, H, Sq, Dv)."""
    B, H, Sq, D = q.shape
    _, HK, Skv, Dv = v.shape
    assert H % HK == 0
    scale = logit_scale if logit_scale is not None else D ** -0.5

    bq = min(bq, Sq)
    bk = min(bk, Skv)

    def pad(x, blk, axis):
        p = (-x.shape[axis]) % blk
        if p == 0:
            return x
        widths = [(0, 0)] * x.ndim
        widths[axis] = (0, p)
        return jnp.pad(x, widths)

    q_, k_, v_ = pad(q, bq, 2), pad(k, bk, 2), pad(v, bk, 2)
    nq, nk = q_.shape[2] // bq, k_.shape[2] // bk

    kernel = functools.partial(_flash_kernel, scale=scale, causal=causal,
                               window=window, bq=bq, bk=bk, nk=nk, skv=Skv)
    grid = (B, H, nq, nk)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
            # model layout is (B, S, G, HK, Dh): query head h -> kv head h % HK
            pl.BlockSpec((1, 1, bk, D), lambda b, h, i, j: (b, h % HK, j, 0)),
            pl.BlockSpec((1, 1, bk, Dv), lambda b, h, i, j: (b, h % HK, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, Dv), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, q_.shape[2], Dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),       # m (running max)
            pltpu.VMEM((bq,), jnp.float32),       # l (running denom)
            pltpu.VMEM((bq, Dv), jnp.float32),    # acc (running numerator)
        ],
        interpret=interpret,
    )(q_, k_, v_)
    return out[:, :, :Sq]
