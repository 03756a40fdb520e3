"""Symmetric per-channel weight quantization of parameter pytrees.

A quantized weight is a ``QTensor`` — a registered pytree node holding the
integer codes ``q`` and the per-output-channel ``scale`` (f32), so quantized
param trees flow through ``jax.tree.map`` slicing (core/partition.py) and
``lax.scan`` layer unstacking (models/model.py) unchanged.

Modes:
  "w8wo" — int8 weight-only (activations stay in compute dtype)
  "w4"   — int4 weight-only, two codes packed per uint8 along the
           contraction axis (axis -2: every dense weight here is (in, out))
  "w8a8" — int8 weights + dynamic per-row int8 activations; dispatched to
           the Pallas int8 matmul (kernels/quant_matmul.py) via
           models/layers.py::dense -> kernels/ops.py::quantized_dense

All modes are symmetric: scale = amax / qmax over the contraction axis, so
dequantization is a single broadcast multiply.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, FrozenSet, Tuple

import jax
import jax.numpy as jnp

MODES = ("w8wo", "w4", "w8a8")
_QMAX = {8: 127, 4: 7}

# dense-projection leaf names consumed via layers.dense (plain ``x @ w``
# with w of shape (..., in, out)); weights consumed otherwise (MoE experts
# by the grouped matmul, MLA up-projections, SSM/LRU mixers) and
# embeddings stay full precision (quantize_tree additionally excludes the
# whole moe subtree).
DENSE_WEIGHTS: FrozenSet[str] = frozenset(
    {"wq", "wk", "wv", "wo", "w_up", "w_gate", "w_down", "lm_head"})


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class QTensor:
    """Quantized weight leaf: integer codes + per-channel f32 scale.

    ``q``: int8 (w8wo/w8a8) or uint8 nibble-packed (w4, contraction axis
    halved); ``scale``: f32 of shape (..., G, out) where G is the number of
    scale groups along the contraction axis (G=1 for the int8 per-channel
    modes, contraction/32 for w4 group-wise). ``bits``/``act_bits`` are
    static aux data and survive tracing.
    """
    q: jax.Array
    scale: jax.Array
    bits: int = 8
    act_bits: int = 0

    def tree_flatten(self):
        return (self.q, self.scale), (self.bits, self.act_bits)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], children[1], *aux)

    @property
    def shape(self) -> Tuple[int, ...]:
        s = tuple(self.q.shape)
        if self.bits == 4:
            s = s[:-2] + (s[-2] * 2, s[-1])
        return s

    @property
    def nbytes(self) -> int:
        return int(self.q.size * self.q.dtype.itemsize
                   + self.scale.size * self.scale.dtype.itemsize)

    def dequantize(self) -> jax.Array:
        q = _unpack_int4(self.q) if self.bits == 4 else self.q
        d, n = q.shape[-2], q.shape[-1]
        groups = self.scale.shape[-2]
        qg = q.astype(jnp.float32).reshape(*q.shape[:-2], groups,
                                           d // groups, n)
        out = qg * self.scale[..., :, None, :]
        return out.reshape(*q.shape[:-2], d, n)


def _pack_int4(q: jax.Array) -> jax.Array:
    """int8 codes in [-8, 7], (..., d, n) -> uint8 nibbles (..., d//2, n)."""
    u = q.astype(jnp.int32) & 0xF
    lo, hi = u[..., 0::2, :], u[..., 1::2, :]
    return (lo | (hi << 4)).astype(jnp.uint8)


def _unpack_int4(packed: jax.Array) -> jax.Array:
    """uint8 nibbles (..., d2, n) -> sign-extended int8 codes (..., d2*2, n)."""
    p = packed.astype(jnp.int32)
    lo, hi = p & 0xF, (p >> 4) & 0xF
    lo = jnp.where(lo >= 8, lo - 16, lo)
    hi = jnp.where(hi >= 8, hi - 16, hi)
    pair = jnp.stack([lo, hi], axis=-2)                  # (..., d2, 2, n)
    out = pair.reshape(*packed.shape[:-2], packed.shape[-2] * 2,
                       packed.shape[-1])
    return out.astype(jnp.int8)


W4_GROUP = 32   # contraction-axis scale-group size for int4 (AWQ-style)


def quantize(w: jax.Array, mode: str) -> QTensor:
    """Symmetric quantization of one (..., in, out) weight.

    Scales are per output channel; w4 additionally groups the contraction
    axis (W4_GROUP rows per scale) — 15 int4 levels need finer scale
    granularity than a whole-column amax to stay usable.
    """
    if mode not in MODES:
        raise ValueError(f"unknown quant mode {mode!r}; known: {MODES}")
    bits = 4 if mode == "w4" else 8
    act_bits = 8 if mode == "w8a8" else 0
    qmax = _QMAX[bits]
    d, n = w.shape[-2], w.shape[-1]
    g = W4_GROUP if (bits == 4 and d % W4_GROUP == 0) else d
    wf = w.astype(jnp.float32).reshape(*w.shape[:-2], d // g, g, n)
    amax = jnp.max(jnp.abs(wf), axis=-2, keepdims=True)
    scale = jnp.maximum(amax, 1e-8) / qmax           # (..., G, 1, n)
    q = jnp.clip(jnp.round(wf / scale), -qmax, qmax).astype(jnp.int8)
    q = q.reshape(*w.shape[:-2], d, n)
    scale = scale[..., 0, :]                         # (..., G, n)
    if bits == 4:
        if d % 2:
            raise ValueError(f"w4 needs an even contraction dim, got {w.shape}")
        q = _pack_int4(q)
    return QTensor(q, scale, bits, act_bits)


def quantize_act(x: jax.Array):
    """Dynamic per-row int8 activation quantization (contraction = last axis).

    Returns (q int8, scale f32 with last axis reduced to 1)."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1, keepdims=True)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int8)
    return q, scale


def quantize_tree(tree: Dict, mode: str,
                  names: FrozenSet[str] = DENSE_WEIGHTS) -> Dict:
    """Quantize every dense-projection leaf of a param tree.

    Selection is by leaf name (the plan key), not shape: only weights the
    models consume through ``layers.dense`` are converted, so
    einsum-consumed params keep their layout. The ``moe`` subtree is
    excluded wholesale — routed expert weights reuse the dense-MLP leaf
    names but are consumed in float by the grouped matmul (models/moe.py),
    so every version tree can share one copy of them.
    Leading stacking dims pass through — scale and codes both keep the
    (layers, ...) prefix that scan/slicing expect.
    """
    _check_mode(mode)
    return _map_weights(tree, names, lambda w: quantize(w, mode))


def quantize_trees(tree: Dict, modes: Tuple[str, ...],
                   names: FrozenSet[str] = DENSE_WEIGHTS) -> Dict[str, Dict]:
    """``quantize_tree`` for each of ``modes``, in one jitted call over the
    weights it converts alone: every other leaf of each result is the
    input tree's own array, shared and not copied (so the float experts of
    a MoE model stay one copy across its versions)."""
    for mode in modes:
        _check_mode(mode)
    weights = []
    _map_weights(tree, names, weights.append)
    codes = _quantize_weights(tuple(weights), tuple(modes))
    out = {}
    for mode in modes:
        it = iter(codes[mode])
        out[mode] = _map_weights(tree, names, lambda w: next(it))
    return out


@functools.partial(jax.jit, static_argnums=1)
def _quantize_weights(weights, modes):
    return {mode: [quantize(w, mode) for w in weights] for mode in modes}


def _check_mode(mode: str):
    if mode not in MODES:
        raise ValueError(f"unknown quant mode {mode!r}; known: {MODES}")


def _map_weights(tree: Dict, names: FrozenSet[str], fn) -> Dict:
    """``tree`` with ``fn`` applied to each weight ``quantize_tree``
    converts, in a fixed order; every other leaf passes through as is."""
    def rec(node, key):
        if key == "moe":
            return node
        if isinstance(node, dict):
            return {k: rec(v, k) for k, v in node.items()}
        if key in names and getattr(node, "ndim", 0) >= 2:
            return fn(node)
        return node
    return rec(tree, "")


def dequantize_tree(tree):
    """Inverse of quantize_tree: QTensor leaves -> f32 dense weights."""
    return jax.tree.map(
        lambda x: x.dequantize() if isinstance(x, QTensor) else x,
        tree, is_leaf=lambda x: isinstance(x, QTensor))


def tree_weight_bytes(tree) -> int:
    """Actual bytes of a (possibly partially quantized) param tree."""
    total = 0
    for leaf in jax.tree.leaves(
            tree, is_leaf=lambda x: isinstance(x, QTensor)):
        if isinstance(leaf, QTensor):
            total += leaf.nbytes
        else:
            total += int(leaf.size * leaf.dtype.itemsize)
    return total
