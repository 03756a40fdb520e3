"""Grouped matmul (rows sorted by group, one weight matrix per group) as a
Pallas TPU kernel: the expert FFNs of a dropless MoE layer.

``lhs`` (M, K) holds the rows of G groups one after another, each group
starting on a row tile of ``ROW_TILE`` rows: group g's rows lie in
``[starts[g], starts[g] + size_g)`` and rows up to ``starts[g + 1]`` are
padding. Rows from ``starts[G]`` on belong to no group. ``rhs`` (G, K, N)
holds one (K, N) matrix per group, and row r of group g gives
``out[r] = lhs[r] @ rhs[g]``. ``padded_starts`` lays the groups out from
their sizes.

Grid = (N blocks, row tiles, K blocks), K innermost. Each row tile's group
comes by scalar prefetch (``tile_group``), with the number of live tiles;
the weight block of step (j, i, k) is ``rhs[tile_group[i], k, j]``, so
the tiles of one group read its weights once per (N, K) block when K is
whole. Tiles past the live rows do no MXU work and issue no DMA: their
index maps name the last live tile's blocks, which are already in VMEM.
Their output rows are left unwritten, so rows that belong to no group
hold no defined value.

``gmm_plan`` derives the blocks from the shapes. TPU is the TARGET;
correctness is validated on CPU with interpret=True against
``kernels/ref.py::grouped_matmul_ref``.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import obs

ROW_TILE = 128          # rows of a tile; each group starts on one
LANES = 128
# most bytes of one weight block: a float32 expert matrix of 2304 x 896
# fits whole, so the tiles of one group fetch it once
_W_BYTES = 9 << 20
_DEFAULT_VMEM = 16 << 20


class GmmPlan(NamedTuple):
    """Tiling of one ``grouped_matmul`` call (see ``gmm_plan``)."""
    tm: int
    tk: int
    tn: int
    grid: tuple                 # (N blocks, row tiles, K blocks)
    vmem_bytes: int


def _blocks(dim: int, most: int) -> int:
    """The largest block of ``dim`` that is a multiple of LANES, divides
    it and is at most ``most`` (at least LANES); ``dim`` itself where it
    is no multiple of LANES."""
    if dim % LANES:
        return dim
    n = dim // LANES
    fits = [b for b in range(1, n + 1) if n % b == 0 and b * LANES <= most]
    return LANES * max(fits, default=1)


def gmm_plan(M: int, K: int, N: int, itemsize: int = 4) -> GmmPlan:
    """Blocks and grid of ``grouped_matmul``, a pure function of the
    operand shapes. K stays whole where a (K, LANES) weight block fits
    the budget; N blocks are as wide as the budget allows."""
    tm = ROW_TILE
    tk = _blocks(K, _W_BYTES // (LANES * itemsize))
    tn = _blocks(N, _W_BYTES // (tk * itemsize))
    nk = K // tk
    work = 2 * itemsize * (tm * tk + tk * tn + tm * tn) \
        + (4 * tm * tn if nk > 1 else 0)
    vmem = max(_DEFAULT_VMEM, _round_up(work + (4 << 20), 1 << 20))
    return GmmPlan(tm, tk, tn, (N // tn, -(-M // tm), nk), vmem)


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def padded_starts(group_sizes, tm: int = ROW_TILE):
    """(G + 1,) int32: the first row of each group when every group starts
    on a tile of ``tm`` rows, then the end of the live rows."""
    padded = -(-group_sizes.astype(jnp.int32) // tm) * tm
    return jnp.concatenate([jnp.zeros((1,), jnp.int32),
                            jnp.cumsum(padded, dtype=jnp.int32)])


def rows_bound(rows: int, groups: int, tm: int = ROW_TILE) -> int:
    """Rows of a buffer that holds ``rows`` rows in ``groups`` groups,
    each group padded to whole tiles, whatever the group sizes."""
    return _round_up(rows + groups * (tm - 1), tm)


def _gmm_kernel(tile_group_ref, n_live_ref, x_ref, w_ref, o_ref, *scratch,
                nk: int):
    del tile_group_ref
    i, k = pl.program_id(1), pl.program_id(2)
    live = i < n_live_ref[0]

    def dot():
        return jax.lax.dot_general(x_ref[...], w_ref[0],
                                   (((1,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32)

    if nk == 1:
        @pl.when(live)
        def _one():
            o_ref[...] = dot().astype(o_ref.dtype)
        return

    acc_ref, = scratch

    @pl.when(live & (k == 0))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(live)
    def _step():
        acc_ref[...] += dot()

    @pl.when(live & (k == nk - 1))
    def _finalize():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def grouped_matmul(lhs, rhs, starts, *, interpret: bool = False):
    """lhs (M, K) rows laid out by ``starts`` (G + 1,); rhs (G, K, N).
    Returns (M, N): each live row times its group's matrix. M must be a
    multiple of ``ROW_TILE``."""
    M, K = lhs.shape
    G, K2, N = rhs.shape
    assert K == K2 and starts.shape == (G + 1,), (lhs.shape, rhs.shape,
                                                  starts.shape)
    assert M % ROW_TILE == 0, M
    plan = gmm_plan(M, K, N, lhs.dtype.itemsize)
    tm, tk, tn = plan.tm, plan.tk, plan.tn
    nn, nm, nk = plan.grid
    # at trace time only, one count per compiled shape: the steps that
    # can do MXU work when every row is live (the dropless worst case
    # fills M less the groups' padding), and all steps
    live_bound = min(nm, -(-max(M - G * (tm - 1), 0) // tm) + G - 1)
    obs.inc("grouped_matmul.grid_steps", live_bound * nn * nk,
            total=nm * nn * nk, experts_held=G)

    tile_start = jnp.arange(nm, dtype=jnp.int32) * tm
    tile_group = jnp.clip(jnp.searchsorted(starts[1:], tile_start,
                                           side="right"),
                          0, G - 1).astype(jnp.int32)
    n_live = (starts[G:] // tm).astype(jnp.int32)

    def last_live(i, n_live_ref):
        return jnp.minimum(i, jnp.maximum(n_live_ref[0] - 1, 0))

    def x_map(j, i, k, tg, nl):
        return (last_live(i, nl), k)

    def w_map(j, i, k, tg, nl):
        return (tg[last_live(i, nl)], k, j)

    def o_map(j, i, k, tg, nl):
        return (last_live(i, nl), j)

    return pl.pallas_call(
        functools.partial(_gmm_kernel, nk=nk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=plan.grid,
            in_specs=[pl.BlockSpec((tm, tk), x_map),
                      pl.BlockSpec((1, tk, tn), w_map)],
            out_specs=pl.BlockSpec((tm, tn), o_map),
            scratch_shapes=([pltpu.VMEM((tm, tn), jnp.float32)]
                            if nk > 1 else [])),
        out_shape=jax.ShapeDtypeStruct((M, N), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=plan.vmem_bytes),
        interpret=interpret,
    )(tile_group, n_live, lhs, rhs)
