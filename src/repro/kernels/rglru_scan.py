"""RG-LRU diagonal linear recurrence as a Pallas TPU kernel.

h_t = a_t * h_{t-1} + gx_t, with a/gx precomputed by cheap jnp projections
(the gates are matmuls XLA already fuses well); the kernel owns the
memory-bound sequential hot loop, keeping the (bw,) state in VMEM scratch
across the sequential chunk grid dim.

Layout: a, gx: (B, S, W). grid = (B, W/bw, S/bc). A width or length that
is not a multiple of its block is padded with identity steps (a=1, gx=0),
which leave the state, and so h_last, unchanged.
Oracle: kernels/ref.py rglru_scan_ref (associative_scan).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rglru_kernel(a_ref, gx_ref, y_ref, hout_ref, h_scr, *, bc: int, nc: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    def step(t, h):
        h = (a_ref[0, t].astype(jnp.float32) * h
             + gx_ref[0, t].astype(jnp.float32))
        y_ref[0, t] = h.astype(y_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, bc, step, h_scr[...])
    h_scr[...] = h

    @pl.when(ic == nc - 1)
    def _finalize():
        hout_ref[0, 0] = h.astype(hout_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bw", "bc", "interpret"))
def rglru_scan(a, gx, *, bw: int = 256, bc: int = 128,
               interpret: bool = False):
    """a, gx: (B, S, W) -> (h_seq (B,S,W), h_last (B,W))."""
    B, S, W = a.shape
    bw = min(bw, W)
    bc = min(bc, S)
    ps, pw = (-S) % bc, (-W) % bw
    if ps or pw:
        a = jnp.pad(a, ((0, 0), (0, ps), (0, pw)), constant_values=1)
        gx = jnp.pad(gx, ((0, 0), (0, ps), (0, pw)))
    nw, nc = (W + pw) // bw, (S + ps) // bc

    kernel = functools.partial(_rglru_kernel, bc=bc, nc=nc)
    y, h = pl.pallas_call(
        kernel,
        grid=(B, nw, nc),
        in_specs=[
            pl.BlockSpec((1, bc, bw), lambda b, w, c: (b, c, w)),
            pl.BlockSpec((1, bc, bw), lambda b, w, c: (b, c, w)),
        ],
        out_specs=[
            pl.BlockSpec((1, bc, bw), lambda b, w, c: (b, c, w)),
            pl.BlockSpec((1, 1, bw), lambda b, w, c: (b, 0, w)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(a.shape, a.dtype),
            jax.ShapeDtypeStruct((B, 1, W + pw), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bw,), jnp.float32)],
        interpret=interpret,
    )(a, gx)
    return y[:, :S, :W], h[:, 0, :W]
