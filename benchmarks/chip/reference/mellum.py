"""Plain forward pass of Mellum2-12B-A2.5B, for the benchmark's
correctness check.

Straightforward ``jax.numpy`` in float32: no kernels, no cache, no
batching, and nothing imported from the program under test. It follows
the published config.json (JetBrains/Mellum2-12B-A2.5B-Instruct):

    h = x + Wo . Attn_t(RMSNorm(x));      y = h + MoE(RMSNorm(h))
    Attn: q = x Wq (H x Dh), k = x Wk, v = x Wv (HK x Dh); RoPE_t on q and
          k (rotate-half); softmax(q . k / sqrt(Dh)), causal; query head h
          reads kv head h // (H / HK); a sliding layer (layer_types) lets
          query i see key j iff 0 <= i - j < sliding_window
    RoPE_t: sliding layers the default rotation at their rope_theta; full
          layers YaRN (Hugging Face _compute_yarn_parameters, truncated):
          frequency j of theta^(-2j/Dh) kept below the correction range of
          beta_fast .. beta_slow rotations over the original context,
          divided by factor above it, a linear ramp between; cos and sin
          both times attention_factor
    MoE:  p = softmax(RMSNorm(h) Wr) over all E routed experts; T = the
          top k of p; w_e = p_e / sum_T p (norm_topk_prob); out = sum over
          e in T held here of w_e (silu(x Wg_e) * x Wu_e) Wd_e
    final: RMSNorm, then x . lm_head (untied)

Expert parallelism: the weights hold the ``held`` experts this chip
serves (experts 0 .. held - 1 of E); routing is over all E, and the layer
returns the held experts' part, as the deployment's chip does. The
held experts are computed densely on every token, weighted by the
routing weight (zero where the token did not pick the expert).

Weights are one dict of stacked arrays, layer axis first, linear weights
as (in, out), query heads in the standard order:

    embed (V, d)   lm_head (d, V)   final_norm (d,)   ln1, ln2 (L, d)
    wq (L, d, H*Dh)   wk, wv (L, d, HK*Dh)   wo (L, H*Dh, d)
    router (L, d, E)   w_gate, w_up (L, held, d, F)   w_down (L, held, F, d)

A served version changes the four attention projections of every layer
and the output head, as the program's quantize_tree does; the experts
and the router stay float32 in every version (see ``reference/qwen.py``
for the versions: f32, w8 with int8 inputs and an int8 cut, w4, and the
w8 control w4a8). The layers run one jitted call each, attention in
blocks of query rows, so the reference fits beside the benchmark's
weights at 8192 tokens.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from reference import qwen as Q

LINEAR = ("wq", "wk", "wv", "wo", "lm_head")
Q_BLOCK = 512
rel_err, widest_gap = Q.rel_err, Q.widest_gap


def dims(cfg: dict) -> dict:
    """The sizes the forward needs, from a configuration file's keys."""
    L = int(cfg["num_hidden_layers"])
    rope = cfg["rope_parameters"]
    full, sliding = rope["full_attention"], rope["sliding_attention"]
    assert full["rope_type"] == "yarn" and sliding["rope_type"] == "default"
    return {"d": int(cfg["hidden_size"]),
            "H": int(cfg["num_attention_heads"]),
            "HK": int(cfg["num_key_value_heads"]),
            "Dh": int(cfg["head_dim"]), "V": int(cfg["vocab_size"]),
            "L": L, "F": int(cfg["moe_intermediate_size"]),
            "E": int(cfg["published"]["num_experts"]),
            "held": int(cfg["num_experts"]),
            "K": int(cfg["num_experts_per_tok"]),
            "W": int(cfg["sliding_window"]),
            "full": tuple(t == "full_attention"
                          for t in cfg["layer_types"][:L]),
            "theta_full": float(full["rope_theta"]),
            "theta_sliding": float(sliding["rope_theta"]),
            "yarn": (float(full["factor"]),
                     int(full["original_max_position_embeddings"]),
                     float(full["beta_fast"]), float(full["beta_slow"]),
                     float(full["attention_factor"])),
            "eps": float(cfg["rms_norm_eps"])}


def version_weights(w: dict, version: str) -> dict:
    """The weights one version serves: the attention projections and the
    head quantized, every other leaf shared with the float weights."""
    if version == "f32":
        return w
    out = dict(w)
    out.update(Q._quantized({k: w[k] for k in LINEAR}, version))
    return out


def yarn_inv_freq(Dh: int, theta: float, yarn) -> np.ndarray:
    """YaRN inverse frequencies, closed form (float64 on the host)."""
    factor, orig, beta_fast, beta_slow, _ = yarn

    def corr(rotations):
        return Dh * math.log(orig / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))

    lo = max(math.floor(corr(beta_fast)), 0)
    hi = min(math.ceil(corr(beta_slow)), Dh - 1)
    j = np.arange(Dh // 2, dtype=np.float64)
    inv = theta ** (-2.0 * j / Dh)
    ramp = np.clip((j - lo) / max(hi - lo, 1e-3), 0.0, 1.0)
    return inv / factor * ramp + inv * (1.0 - ramp)


def _rope(x, inv, mult: float):
    """x: (S, heads, Dh), positions 0..S-1, rotate-half convention."""
    S, half = x.shape[0], x.shape[-1] // 2
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[:, None] * mult, jnp.sin(ang)[:, None] * mult
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def _rms(x, scale, dtype, eps):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(dtype)


def _attention(dm, q, k, v, window, prec, dtype):
    """Causal (and windowed) attention in blocks of Q_BLOCK query rows.
    q: (S, H, Dh); k, v: (S, HK, Dh)."""
    S, H, Dh = q.shape
    HK = k.shape[1]
    G = H // HK
    nb = -(-S // Q_BLOCK)
    qp = jnp.pad(q, ((0, nb * Q_BLOCK - S), (0, 0), (0, 0)))
    qb = qp.reshape(nb, Q_BLOCK, HK, G, Dh)
    j = jnp.arange(S)

    def block(args):
        qi, b = args
        i = b * Q_BLOCK + jnp.arange(Q_BLOCK)
        scores = jnp.einsum("ikgd,jkd->kgij", qi, k, precision=prec,
                            preferred_element_type=jnp.float32) * Dh ** -0.5
        seen = j[None, :] <= i[:, None]
        if window is not None:
            seen &= (i[:, None] - j[None, :]) < window
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf),
                               axis=-1).astype(dtype)
        return jnp.einsum("kgij,jkd->ikgd", probs, v, precision=prec,
                          preferred_element_type=jnp.float32).astype(dtype)

    out = jax.lax.map(block, (qb, jnp.arange(nb)))
    return out.reshape(nb * Q_BLOCK, H * Dh)[:S]


def _moe(dm, lw, h, prec, dtype):
    """The held experts' part of the routed experts, and the rows each
    held expert was routed (held,)."""
    logits = jnp.matmul(h.astype(jnp.float32),
                        lw["router"].astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    p = jax.nn.softmax(logits, axis=-1)                        # (S, E)
    top_p, top_e = jax.lax.top_k(p, dm["K"])
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    pick = jax.nn.one_hot(top_e, dm["E"], dtype=jnp.float32)   # (S, K, E)
    wts = jnp.sum(top_p[..., None] * pick, axis=1)[:, :dm["held"]]
    g = jnp.einsum("sd,edf->esf", h, lw["w_gate"].astype(dtype),
                   precision=prec, preferred_element_type=jnp.float32)
    u = jnp.einsum("sd,edf->esf", h, lw["w_up"].astype(dtype),
                   precision=prec, preferred_element_type=jnp.float32)
    # each expert's hidden rows weighted before the down projection, so
    # the sum over experts needs no (held, S, d) intermediate
    m = (jax.nn.silu(g) * u * wts.T[:, :, None]).astype(dtype)
    out = jnp.einsum("esf,efd->sd", m, lw["w_down"].astype(dtype),
                     precision=prec,
                     preferred_element_type=jnp.float32).astype(dtype)
    rows = jnp.sum(pick, axis=(0, 1))[:dm["held"]].astype(jnp.int32)
    return out, rows


def _layer(dm: dict, dtype, prec, lw: dict, full, x):
    """One decoder layer on x: (S, d); ``full`` is static."""
    S = x.shape[0]
    H, HK, Dh, eps = dm["H"], dm["HK"], dm["Dh"], dm["eps"]
    h = _rms(x, lw["ln1"], dtype, eps)
    q = Q._linear(h, lw["wq"], dtype, prec).reshape(S, H, Dh)
    k = Q._linear(h, lw["wk"], dtype, prec).reshape(S, HK, Dh)
    v = Q._linear(h, lw["wv"], dtype, prec).reshape(S, HK, Dh)
    if full:
        inv = jnp.asarray(yarn_inv_freq(Dh, dm["theta_full"], dm["yarn"]),
                          jnp.float32)
        mult = dm["yarn"][4]
    else:
        inv = jnp.asarray(dm["theta_sliding"] ** (
            -2.0 * np.arange(Dh // 2) / Dh), jnp.float32)
        mult = 1.0
    q, k = _rope(q, inv, mult), _rope(k, inv, mult)
    att = _attention(dm, q, k, v, None if full else dm["W"], prec, dtype)
    x = x + Q._linear(att, lw["wo"], dtype, prec)
    m, rows = _moe(dm, lw, _rms(x, lw["ln2"], dtype, eps), prec, dtype)
    return x + m, rows


@functools.lru_cache(maxsize=None)
def _programs(dm_items: tuple, dtype_name: str, precision: str):
    dm, dtype = dict(dm_items), jnp.dtype(dtype_name)
    prec = jax.lax.Precision(precision)

    @jax.jit
    def embed(table, tokens):
        return jnp.take(table, tokens, axis=0).astype(dtype)

    @functools.partial(jax.jit, static_argnums=(2,))
    def layer(layers, i, full, x):
        lw = jax.tree.map(lambda a: a[i], layers)
        return _layer(dm, dtype, prec, lw, full, x)

    @jax.jit
    def link(x):
        xq, xs = Q.quantize_rows(x)
        return (xq * xs).astype(dtype)

    @jax.jit
    def head(final_norm, lm_head, x, rows):
        h = _rms(x[rows], final_norm, dtype, dm["eps"])
        return Q._linear(h, lm_head, dtype, prec).astype(jnp.float32)

    return embed, layer, link, head


def forward(cfg: dict, w: dict, tokens, *, rows, cut=None,
            link_int8: bool = False, dtype="float32", expert_rows=False):
    """Logits (len(rows), V) at positions ``rows`` of one sequence, and
    with ``expert_rows`` also the rows routed to each held expert of each
    layer, (L, held) on the host.

    ``w`` holds one version's weights (``version_weights``). With
    ``link_int8`` the activation entering layer ``cut`` crosses the cut
    as int8 per row, as the split engine's w8 version ships it. Values
    are stored in ``dtype``; products of float values run in full float32
    ("highest") for float32 and at the chip's default precision for a
    lower type (the control); products of integer codes always run exact.
    The router's product always runs in full float32."""
    dm = dims(cfg)
    precision = "highest" if jnp.dtype(dtype) == jnp.float32 else "default"
    embed, layer, link, head = _programs(tuple(sorted(dm.items())),
                                         jnp.dtype(dtype).name, precision)
    layers = {k: v for k, v in w.items()
              if k not in ("embed", "lm_head", "final_norm")}
    x = embed(w["embed"], jnp.asarray(tokens, jnp.int32))
    counts = []
    for i in range(dm["L"]):
        if link_int8 and cut == i:
            x = link(x)
        x, n = layer(layers, jnp.int32(i), dm["full"][i], x)
        counts.append(n)
    if link_int8 and cut == dm["L"]:
        x = link(x)
    logits = head(w["final_norm"], w["lm_head"], x,
                  jnp.asarray(np.asarray(rows), jnp.int32))
    if expert_rows:
        return logits, np.asarray(jnp.stack(counts))
    return logits
