"""Plain forward pass of the Qwen2 / Qwen3 dense decoder, for the
benchmark's correctness check.

Straightforward ``jax.numpy`` in float32: no kernels, no cache, no
batching, and nothing imported from the program under test. It follows
the published architecture (Qwen2 technical report, arXiv:2407.10671;
Qwen3 model card): RMSNorm before attention and MLP, grouped-query
attention with rotary embeddings (rotate-half, base ``rope_theta``),
optional QKV bias (Qwen2) and per-head RMSNorm on q and k before the
rotation (Qwen3), SwiGLU MLP, a final RMSNorm and an output head tied to
the embedding.

Weights are one dict of stacked arrays, layer axis first, linear weights
as (in, out), query heads in the standard order (query head h reads kv
head h // (H / HK)):

    embed (V, d)   final_norm (d,)   ln1, ln2 (L, d)
    wq (L, d, H*Dh)   bq (L, H*Dh)   wk, wv (L, d, HK*Dh)   bk, bv (L, HK*Dh)
    q_norm, k_norm (L, Dh)   wo (L, H*Dh, d)
    w_gate, w_up (L, d, F)   w_down (L, F, d)

A served *version* changes the seven linear projections of every layer:

    f32   float weights;
    w8    int8 codes per output channel, and every projection's input
          quantized to int8 per row (w8a8); the activation at the cut is
          shipped as int8 per row as well;
    w4    int4 codes in groups of 32 along the contraction axis,
          dequantized to float32 (weight only);
    w4a8  int4 codes per output channel with int8 inputs: the step below
          w8, used only as its control.

The layers run one jitted call each, so the reference fits beside
whatever else the process holds.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

LINEAR = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")
EPS = 1e-6
W4_GROUP = 32


def dims(cfg: dict) -> dict:
    """The sizes the forward needs, from a configuration file's keys."""
    d = int(cfg["hidden_size"])
    h = int(cfg["num_attention_heads"])
    return {"d": d, "H": h, "HK": int(cfg["num_key_value_heads"]),
            "Dh": int(cfg.get("head_dim") or d // h),
            "F": int(cfg["intermediate_size"]),
            "V": int(cfg["vocab_size"]), "L": int(cfg["num_hidden_layers"]),
            "theta": float(cfg["rope_theta"]),
            "bias": bool(cfg.get("qkv_bias", False)),
            "qk_norm": bool(cfg.get("qk_norm", False))}


# --------------------------------------------------------------------------
# quantized versions of the linear weights
# --------------------------------------------------------------------------

def _codes(w, qmax: int, group: int):
    """Symmetric codes and scales of (..., in, out) weights, one scale per
    output channel and group of ``group`` rows of the contraction axis."""
    d, n = w.shape[-2], w.shape[-1]
    wg = w.astype(jnp.float32).reshape(*w.shape[:-2], d // group, group, n)
    scale = jnp.maximum(jnp.max(jnp.abs(wg), axis=-2, keepdims=True),
                        1e-8) / qmax
    q = jnp.clip(jnp.round(wg / scale), -qmax, qmax)
    return q, scale


@functools.partial(jax.jit, static_argnums=1)
def _quantized(lin: dict, version: str) -> dict:
    out = {}
    for name, a in lin.items():
        d = a.shape[-2]
        if version == "w4":
            group = W4_GROUP if d % W4_GROUP == 0 else d
            q, s = _codes(a, 7, group)
            out[name] = (q * s).reshape(a.shape)
        elif version in ("w8", "w4a8"):
            q, s = _codes(a, 127 if version == "w8" else 7, d)
            out[name] = {"q": q.reshape(a.shape).astype(jnp.int8),
                         "s": s.reshape(*a.shape[:-2], a.shape[-1])}
        else:
            raise ValueError(f"unknown version {version!r}")
    return out


def version_weights(w: dict, version: str) -> dict:
    """The weights one version serves, derived from the float weights
    (the other leaves are shared, not copied)."""
    if version == "f32":
        return w
    out = dict(w)
    out.update(_quantized({k: w[k] for k in LINEAR}, version))
    return out


def quantize_rows(x):
    """Per-row symmetric int8 codes of x, returned dequantized."""
    xf = x.astype(jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(xf), axis=-1, keepdims=True),
                    1e-8) / 127.0
    return jnp.clip(jnp.round(xf / s), -127, 127), s


def _linear(x, w, dtype, prec):
    if isinstance(w, dict):               # int8 inputs x integer codes
        xq, xs = quantize_rows(x)
        y = jnp.matmul(xq, w["q"].astype(jnp.float32),
                       precision=jax.lax.Precision.HIGHEST)
        return (y * xs * w["s"]).astype(dtype)
    return jnp.matmul(x.astype(dtype), w.astype(dtype), precision=prec)


# --------------------------------------------------------------------------
# the forward
# --------------------------------------------------------------------------

def _rms(x, scale, dtype):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + EPS)
    return (y * scale.astype(jnp.float32)).astype(dtype)


def _rope(x, theta: float):
    """x: (S, heads, Dh), positions 0..S-1, rotate-half convention."""
    S, half = x.shape[0], x.shape[-1] // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def _layer(dm: dict, dtype, prec, lw: dict, x):
    """One decoder layer on x: (S, d)."""
    S = x.shape[0]
    H, HK, Dh = dm["H"], dm["HK"], dm["Dh"]
    G = H // HK
    h = _rms(x, lw["ln1"], dtype)
    q = _linear(h, lw["wq"], dtype, prec)
    k = _linear(h, lw["wk"], dtype, prec)
    v = _linear(h, lw["wv"], dtype, prec)
    if dm["bias"]:
        q = q + lw["bq"].astype(dtype)
        k = k + lw["bk"].astype(dtype)
        v = v + lw["bv"].astype(dtype)
    q, k, v = (q.reshape(S, H, Dh), k.reshape(S, HK, Dh),
               v.reshape(S, HK, Dh))
    if dm["qk_norm"]:
        q = _rms(q, lw["q_norm"], dtype)
        k = _rms(k, lw["k_norm"], dtype)
    q, k = _rope(q, dm["theta"]), _rope(k, dm["theta"])
    qg = q.reshape(S, HK, G, Dh)
    scores = jnp.einsum("ikgd,jkd->kgij", qg, k, precision=prec,
                        preferred_element_type=jnp.float32) * Dh ** -0.5
    causal = jnp.tril(jnp.ones((S, S), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1).astype(dtype)
    att = jnp.einsum("kgij,jkd->ikgd", probs, v, precision=prec,
                     preferred_element_type=jnp.float32).astype(dtype)
    x = x + _linear(att.reshape(S, H * Dh), lw["wo"], dtype, prec)
    h = _rms(x, lw["ln2"], dtype)
    g = _linear(h, lw["w_gate"], dtype, prec)
    u = _linear(h, lw["w_up"], dtype, prec)
    m = (jax.nn.silu(g.astype(jnp.float32))
         * u.astype(jnp.float32)).astype(dtype)
    return x + _linear(m, lw["w_down"], dtype, prec)


@functools.lru_cache(maxsize=None)
def _programs(dm_items: tuple, dtype_name: str, precision: str):
    dm, dtype = dict(dm_items), jnp.dtype(dtype_name)
    prec = jax.lax.Precision(precision)

    @jax.jit
    def embed(table, tokens):
        return jnp.take(table, tokens, axis=0).astype(dtype)

    @jax.jit
    def layer(layers, i, x):
        lw = jax.tree.map(lambda a: a[i], layers)
        return _layer(dm, dtype, prec, lw, x)

    @jax.jit
    def link(x):
        xq, xs = quantize_rows(x)
        return (xq * xs).astype(dtype)

    @jax.jit
    def head(final_norm, table, x, rows):
        h = _rms(x[rows], final_norm, dtype)
        logits = jnp.matmul(h, table.astype(dtype).T, precision=prec)
        return logits.astype(jnp.float32)

    return embed, layer, link, head


def forward(cfg: dict, w: dict, tokens, *, rows, cut=None,
            link_int8: bool = False, dtype="float32"):
    """Logits (len(rows), V) at positions ``rows`` of one sequence.

    ``w`` holds one version's weights (``version_weights``). With
    ``link_int8`` the activation entering layer ``cut`` crosses the cut
    as int8 per row, as the split engine's w8 version ships it. Values
    are stored in ``dtype``; products of float values run in full float32
    ("highest") for float32 and at the chip's default precision for a
    lower type (the control); products of integer codes always run
    exact."""
    dm = dims(cfg)
    precision = "highest" if jnp.dtype(dtype) == jnp.float32 else "default"
    embed, layer, link, head = _programs(tuple(sorted(dm.items())),
                                         jnp.dtype(dtype).name, precision)
    layers = {k: v for k, v in w.items()
              if k not in ("embed", "final_norm")}
    x = embed(w["embed"], jnp.asarray(tokens, jnp.int32))
    for i in range(dm["L"]):
        if link_int8 and cut == i:
            x = link(x)
        x = layer(layers, jnp.int32(i), x)
    return head(w["final_norm"], w["embed"], x,
                jnp.asarray(np.asarray(rows), jnp.int32))


def widest_gap(ref_logits, tokens) -> float:
    """Largest amount by which a chosen token's reference logit lies
    below the reference's best, over rows. ref_logits: (R, V) on host."""
    ref = np.asarray(ref_logits, np.float64)
    tok = np.asarray(tokens, np.int64)
    gaps = ref.max(axis=-1) - ref[np.arange(ref.shape[0]), tok]
    return float(gaps.max()) if gaps.size else 0.0


def rel_err(got, ref) -> float:
    """Relative L2 error of a logits row against the reference's."""
    g = np.asarray(got, np.float64)
    r = np.asarray(ref, np.float64)
    return float(np.linalg.norm(g - r) / np.linalg.norm(r))
