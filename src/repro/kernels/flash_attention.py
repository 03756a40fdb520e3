"""Flash attention (forward) as a Pallas TPU kernel.

Online-softmax attention with explicit BlockSpec VMEM tiling. The model
lays query heads out as (G, HK): query head h = g * HK + hk reads kv head
hk = h % HK. One grid step takes the whole group of G query heads that
share a kv head, stacked as (G * bq, D) rows: grid = (B, HK, num_q_blocks,
num_kv_blocks), and each k/v block is fetched once per kv head rather
than once per query head. q goes in G times, one BlockSpec per head of
the group, so the kernel reads q in the (B, H, S, D) layout the model
writes it in (a (B, G, HK, S, D) reshape of q makes XLA put a relayout
copy before the kernel); the output is written as (B, G, HK, S, Dv),
which is (B, H, S, Dv) in the same memory. The innermost (kv) grid dim
is sequential ("arbitrary") and accumulates (m, l, acc) in VMEM scratch,
m and l replicated across the 128 lanes.

``flash_plan`` derives the tiles from the shapes and lists the live
(q block, kv block) pairs: a pair is dead when causal masking or the
sliding window masks all of it. A dead step computes nothing, and its
k/v index map names a block already in VMEM, so it issues no DMA.
Causal + sliding-window masking is positional. Calls with a window are
the jitted ``flash_attention_window``, so a device trace names them apart.

TPU is the TARGET; correctness is validated on CPU with interpret=True
against kernels/ref.py (pure jnp oracle).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import obs

NEG_INF = -1e30
LANES = 128
# largest float32 working set of one step's q rows: the (G*bq, bk) score
# tile and the (G*bq, D) q tile, well inside the default scoped VMEM
_STEP_BYTES = 2 << 20


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


class FlashPlan(NamedTuple):
    """Tiling of one ``flash_attention`` call (see ``flash_plan``)."""
    bq: int
    bk: int
    grid: tuple                 # (B, HK, nq, nk)
    live_steps: int
    causal: bool
    window: Optional[int]

    @property
    def total_steps(self) -> int:
        return int(np.prod(self.grid))

    def kv_range(self, i, xp=jnp):
        """First and last live kv block of q block ``i``: the kv blocks
        holding a key that some query of block i may attend to. ``i`` is
        a traced grid index (``xp=jnp``) or a numpy array (``xp=np``)."""
        nk = self.grid[3]
        lo = xp.zeros_like(i)
        hi = xp.full_like(i, nk - 1)
        if self.causal:               # a key at or before the block's last query
            hi = xp.minimum(hi, (i * self.bq + self.bq - 1) // self.bk)
        if self.window is not None:   # a key within the window of its first
            lo = xp.maximum(i * self.bq - self.window + 1, 0) // self.bk
        return lo, hi

    def kv_block(self, i, j, xp=jnp):
        """The kv block that step (i, j) reads: j itself when live, else
        the nearest live block, which is already in VMEM (no DMA)."""
        lo, hi = self.kv_range(i, xp)
        return xp.minimum(xp.maximum(j, lo), hi)


def flash_plan(B: int, H: int, HK: int, Sq: int, Skv: int, D: int,
               causal: bool, window: Optional[int]) -> FlashPlan:
    """Block sizes, grid and live step count of ``flash_attention``, a
    pure function of the operand shapes and the masking flags."""
    G = H // HK
    bq = min(128, _round_up(Sq, 16))
    rows = G * bq
    bk_fit = (_STEP_BYTES // (4 * rows) - D) // LANES * LANES
    bk = max(LANES, min(512, _round_up(Skv, LANES), bk_fit))
    nq, nk = -(-Sq // bq), -(-Skv // bk)
    plan = FlashPlan(bq, bk, (B, HK, nq, nk), 0, causal, window)
    lo, hi = plan.kv_range(np.arange(nq), np)
    live = B * HK * int(np.maximum(hi - lo + 1, 0).sum())
    return plan._replace(live_steps=live)


def _lanes(x, n: int):
    """Lane-replicated (rows, LANES) statistics as (rows, n); n is under
    LANES or a multiple of it."""
    return x[:, :n] if n < LANES else jnp.tile(x, (1, n // LANES))


def _flash_kernel(*refs, plan: FlashPlan, scale: float, skv: int):
    *q_refs, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr = refs
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    G = len(q_refs)
    _, _, bq, D = q_refs[0].shape
    bk, Dv = plan.bk, v_ref.shape[-1]
    rows = G * bq

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    lo, hi = plan.kv_range(iq)

    @pl.when((lo <= ik) & (ik <= hi))
    def _step():
        q = jnp.concatenate([r[0, 0].astype(jnp.float32)
                             for r in q_refs])        # (rows, D)
        k = k_ref[0, 0].astype(jnp.float32)          # (bk, D)
        v = v_ref[0, 0].astype(jnp.float32)          # (bk, Dv)

        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale

        mask = _mask(plan, skv, iq, ik, bq)
        if mask is not None:          # one (bq, bk) mask for all G heads
            s = jnp.where(mask[None], s.reshape(G, bq, bk),
                          NEG_INF).reshape(rows, bk)

        m_prev = m_scr[...]                           # (rows, LANES)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - _lanes(m_new, bk))
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * _lanes(alpha, Dv) + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(ik == plan.grid[3] - 1)
    def _finalize():
        out = acc_scr[...] / _lanes(jnp.maximum(l_scr[...], 1e-30), Dv)
        o_ref[0, :, 0] = out.reshape(G, bq, Dv).astype(o_ref.dtype)


def _mask(plan: FlashPlan, skv: int, iq, ik, bq: int):
    """Positions of a (bq, bk) block that may attend, or None if all may."""
    bk = plan.bk
    qpos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    terms = []
    if skv % bk:                      # exclude zero-padded kv slots
        terms.append(kpos < skv)
    if plan.causal:
        terms.append(kpos <= qpos)
    if plan.window is not None:
        terms.append((qpos - kpos) < plan.window)
    return functools.reduce(jnp.logical_and, terms) if terms else None


@functools.partial(jax.jit, static_argnames=("causal", "window",
                                             "interpret", "logit_scale"))
def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None,
                    logit_scale: Optional[float] = None,
                    interpret: bool = False):
    """q: (B, H, Sq, D); k, v: (B, HK, Skv, D). Returns (B, H, Sq, Dv).
    A call with a sliding window runs as ``flash_attention_window``."""
    if window is not None:
        return flash_attention_window(q, k, v, window=window, causal=causal,
                                      logit_scale=logit_scale,
                                      interpret=interpret)
    return _flash(q, k, v, causal, None, logit_scale, interpret)


@functools.partial(jax.jit, static_argnames=("window", "causal",
                                             "interpret", "logit_scale"))
def flash_attention_window(q, k, v, *, window: int, causal: bool = True,
                           logit_scale: Optional[float] = None,
                           interpret: bool = False):
    """``flash_attention`` with a sliding window, under its own name in a
    device trace: its work is the window's band of keys, not the causal
    triangle, so its costs are counted apart."""
    return _flash(q, k, v, causal, window, logit_scale, interpret)


def _flash(q, k, v, causal: bool, window: Optional[int],
           logit_scale: Optional[float], interpret: bool):
    B, H, Sq, D = q.shape
    _, HK, Skv, Dv = v.shape
    assert H % HK == 0
    G = H // HK
    scale = logit_scale if logit_scale is not None else D ** -0.5
    plan = flash_plan(B, H, HK, Sq, Skv, D, causal, window)
    # at trace time only: one count per compiled shape
    obs.inc("flash_attention.grid_steps", plan.live_steps,
            total=plan.total_steps, Sq=Sq, causal=causal)
    bq, bk = plan.bq, plan.bk
    _, _, nq, nk = plan.grid

    def pad(x, length, axis):
        if x.shape[axis] == length:
            return x
        widths = [(0, 0)] * x.ndim
        widths[axis] = (0, length - x.shape[axis])
        return jnp.pad(x, widths)

    q_ = pad(q, nq * bq, 2)
    k_, v_ = pad(k, nk * bk, 2), pad(v, nk * bk, 2)

    def kv_map(b, h, i, j):
        return (b, h, plan.kv_block(i, j), 0)

    kernel = functools.partial(_flash_kernel, plan=plan, scale=scale,
                               skv=Skv)
    out = pl.pallas_call(
        kernel,
        grid=plan.grid,
        in_specs=[
            *[pl.BlockSpec((1, 1, bq, D),
                           lambda b, h, i, j, g=g: (b, g * HK + h, i, 0))
              for g in range(G)],
            pl.BlockSpec((1, 1, bk, D), kv_map),
            pl.BlockSpec((1, 1, bk, Dv), kv_map),
        ],
        out_specs=pl.BlockSpec((1, G, 1, bq, Dv),
                               lambda b, h, i, j: (b, 0, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, G, HK, nq * bq, Dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G * bq, LANES), jnp.float32),   # m (running max)
            pltpu.VMEM((G * bq, LANES), jnp.float32),   # l (running denom)
            pltpu.VMEM((G * bq, Dv), jnp.float32),      # acc (numerator)
        ],
        interpret=interpret,
    )(*[q_] * G, k_, v_)
    return out.reshape(B, H, nq * bq, Dv)[:, :, :Sq]
