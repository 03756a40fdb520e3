"""Mamba-1 selective-scan as a Pallas TPU kernel.

TPU adaptation of the paper's "hardware-aware scan": the GPU version keeps
state in SRAM/registers per thread-block; here the (bd, N) state tile lives
in VMEM scratch and persists across the sequential chunk grid dimension,
while (batch, channel-block) grid dims are parallel. The discretized
(S, d_inner, N) tensor is never materialized in HBM — only per-chunk tiles
stream through VMEM.

Layout: u, dt: (B, S, DI); Bm, Cm: (B, S, N); A: (DI, N).
grid = (B, DI/bd, S/bc); innermost chunk dim is sequential and carries h.
A width or length that is not a multiple of its block is padded with
dt = u = 0 steps (dA = 1, no input), which leave the state unchanged.
Oracle: models/ssm.py ssm_scan_chunked (minus the D-skip, composed in ops).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _mamba_kernel(u_ref, dt_ref, b_ref, c_ref, a_ref, y_ref, hout_ref,
                  h_scr, *, bc: int, nc: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    a = a_ref[...].astype(jnp.float32)            # (bd, N)

    def step(t, h):
        dt_t = dt_ref[0, t].astype(jnp.float32)   # (bd,)
        u_t = u_ref[0, t].astype(jnp.float32)     # (bd,)
        b_t = b_ref[0, t].astype(jnp.float32)     # (N,)
        c_t = c_ref[0, t].astype(jnp.float32)     # (N,)
        dA = jnp.exp(dt_t[:, None] * a)           # (bd, N)
        h = dA * h + (dt_t * u_t)[:, None] * b_t[None, :]
        y_t = jnp.sum(h * c_t[None, :], axis=-1)  # (bd,)
        y_ref[0, t] = y_t.astype(y_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, bc, step, h_scr[...])
    h_scr[...] = h

    @pl.when(ic == nc - 1)
    def _finalize():
        hout_ref[0] = h.astype(hout_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bd", "bc", "interpret"))
def mamba_scan(u, dt, Bm, Cm, A, *, bd: int = 128, bc: int = 128,
               interpret: bool = False):
    """Selective scan. u, dt: (B,S,DI); Bm, Cm: (B,S,N); A: (DI,N).

    Returns (y (B,S,DI), h_final (B,DI,N)). No D-skip/gating (see ops.py).
    """
    B, S, DI = u.shape
    N = Bm.shape[-1]
    bd = min(bd, DI)
    bc = min(bc, S)
    ps, pd = (-S) % bc, (-DI) % bd
    if ps or pd:
        seq = ((0, 0), (0, ps), (0, pd))
        u, dt = jnp.pad(u, seq), jnp.pad(dt, seq)
        Bm = jnp.pad(Bm, ((0, 0), (0, ps), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, ps), (0, 0)))
        A = jnp.pad(A, ((0, pd), (0, 0)))
    nd, nc = (DI + pd) // bd, (S + ps) // bc

    kernel = functools.partial(_mamba_kernel, bc=bc, nc=nc)
    y, h = pl.pallas_call(
        kernel,
        grid=(B, nd, nc),
        in_specs=[
            pl.BlockSpec((1, bc, bd), lambda b, d, c: (b, c, d)),   # u
            pl.BlockSpec((1, bc, bd), lambda b, d, c: (b, c, d)),   # dt
            pl.BlockSpec((1, bc, N), lambda b, d, c: (b, c, 0)),    # Bm
            pl.BlockSpec((1, bc, N), lambda b, d, c: (b, c, 0)),    # Cm
            pl.BlockSpec((bd, N), lambda b, d, c: (d, 0)),          # A
        ],
        out_specs=[
            pl.BlockSpec((1, bc, bd), lambda b, d, c: (b, c, d)),   # y
            pl.BlockSpec((1, bd, N), lambda b, d, c: (b, d, 0)),    # h_final
        ],
        out_shape=[
            jax.ShapeDtypeStruct(u.shape, u.dtype),
            jax.ShapeDtypeStruct((B, DI + pd, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bd, N), jnp.float32)],
        interpret=interpret,
    )(u, dt, Bm, Cm, A)
    return y[:, :S, :DI], h[:, :DI]
