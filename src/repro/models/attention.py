"""Attention blocks: GQA self-attention (bias/qk_norm/RoPE/SWA), MLA
(DeepSeek-V2 compressed KV), and cross-attention (Whisper decoder / VLM).

Cache layout (self-attn): {"k","v"}: (B, C, HK, Dh) ring buffers indexed by
``pos % C`` so sliding-window decode works with C == window. Slot validity is
recovered positionally: slot s holds absolute position
``pos - ((pos - s) mod C)`` (negative => empty).

MLA cache stores the *compressed* latent: {"ckv": (B,C,R), "krope": (B,C,Dr)}.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.params import P
from repro.models import attention_core as ac
from repro.models.layers import apply_rope, dense, rms_norm_headwise


# --------------------------------------------------------------------------
# plans
# --------------------------------------------------------------------------

def plan_self_attn(cfg: ModelConfig):
    d, Dh = cfg.d_model, cfg.resolved_head_dim
    H, HK = cfg.n_heads, cfg.n_kv_heads
    if cfg.use_mla:
        nope, rope = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
        vd, R = cfg.v_head_dim, cfg.kv_lora_rank
        return {
            "wq": P((d, H * (nope + rope)), ("embed", "heads")),
            "w_dkv": P((d, R + rope), ("embed", None)),
            "kv_norm": P((R,), (None,), "ones"),
            "w_uk": P((R, H * nope), (None, "heads")),
            "w_uv": P((R, H * vd), (None, "heads")),
            "wo": P((H * vd, d), ("heads", "embed")),
        }
    plan = {
        "wq": P((d, H * Dh), ("embed", "heads")),
        "wk": P((d, HK * Dh), ("embed", "kv_heads")),
        "wv": P((d, HK * Dh), ("embed", "kv_heads")),
        "wo": P((H * Dh, d), ("heads", "embed")),
    }
    if cfg.qkv_bias:
        plan["bq"] = P((H * Dh,), ("heads",), "zeros")
        plan["bk"] = P((HK * Dh,), ("kv_heads",), "zeros")
        plan["bv"] = P((HK * Dh,), ("kv_heads",), "zeros")
    if cfg.attn_bias:
        plan["bo"] = P((d,), (None,), "zeros")
    if cfg.qk_norm:
        plan["q_norm"] = P((Dh,), (None,), "ones")
        plan["k_norm"] = P((Dh,), (None,), "ones")
    return plan


def plan_cross_attn(cfg: ModelConfig):
    d, Dh = cfg.d_model, cfg.resolved_head_dim
    H, HK = cfg.n_heads, cfg.n_kv_heads
    plan = {
        "wq": P((d, H * Dh), ("embed", "heads")),
        "wk": P((d, HK * Dh), ("embed", "kv_heads")),
        "wv": P((d, HK * Dh), ("embed", "kv_heads")),
        "wo": P((H * Dh, d), ("heads", "embed")),
    }
    if cfg.attn_bias:
        plan["bq"] = P((H * Dh,), ("heads",), "zeros")
        plan["bv"] = P((HK * Dh,), ("kv_heads",), "zeros")
        plan["bo"] = P((d,), (None,), "zeros")
    return plan


# --------------------------------------------------------------------------
# ring-buffer cache helpers
# --------------------------------------------------------------------------

def slot_positions(pos, cache_len: int):
    """Absolute position held by each ring slot after ``pos+1`` tokens
    (current token at ``pos`` already written). Negative => empty slot."""
    s = jnp.arange(cache_len, dtype=jnp.int32)
    return pos - jnp.mod(pos - s, cache_len)


def ring_write_step(buf, val, pos):
    """Write one timestep val (B, ...) at slot pos % C. buf: (B, C, ...)."""
    C = buf.shape[1]
    return jax.lax.dynamic_update_slice_in_dim(
        buf, val[:, None], jnp.mod(pos, C), axis=1)


def ring_from_prefill(seq_vals, cache_len: int):
    """Build a ring buffer from prefill values (B, S, ...): keep the last
    ``cache_len`` positions, placed at their ``p % cache_len`` slots."""
    B, S = seq_vals.shape[:2]
    if S <= cache_len:
        pad = [(0, 0)] * seq_vals.ndim
        pad[1] = (0, cache_len - S)
        return jnp.pad(seq_vals, pad)
    last = seq_vals[:, S - cache_len:]            # positions S-C .. S-1
    # position p sits at slot p % C; last[0] is position S-C
    shift = (S - cache_len) % cache_len
    return jnp.roll(last, shift, axis=1)


# --------------------------------------------------------------------------
# applies
# --------------------------------------------------------------------------

def _heads(x, n, d):
    return x.reshape(x.shape[:-1] + (n, d))


def apply_self_attn(cfg: ModelConfig, p, x, *, pos0, mode: str,
                    cache=None, window: Optional[int] = None,
                    causal: bool = True, cache_len: Optional[int] = None,
                    full=None):
    """Returns (out, new_cache). mode in {train, prefill, decode}.

    Full-attention layers (``window`` None) rotate with the config's YaRN
    frequencies where it has them. In a stack of mixed layer types
    (``cfg.mixed_attention``), ``full`` is the layer's traced type flag:
    the layer runs full attention where it is set, else the config's
    sliding window."""
    if full is not None:
        def branch(w):
            return lambda p, x, cache: apply_self_attn(
                cfg, p, x, pos0=pos0, mode=mode, cache=cache, window=w,
                causal=causal, cache_len=cache_len)
        return jax.lax.cond(full, branch(None), branch(cfg.sliding_window),
                            p, x, cache)
    B, S, _ = x.shape
    if cfg.use_mla:
        return _apply_mla(cfg, p, x, pos0=pos0, mode=mode, cache=cache,
                          window=window, cache_len=cache_len)
    Dh, H, HK = cfg.resolved_head_dim, cfg.n_heads, cfg.n_kv_heads

    q = dense(x, p["wq"])
    k = dense(x, p["wk"])
    v = dense(x, p["wv"])
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q, k, v = _heads(q, H, Dh), _heads(k, HK, Dh), _heads(v, HK, Dh)
    if cfg.qk_norm:
        q = rms_norm_headwise(q, p["q_norm"])
        k = rms_norm_headwise(k, p["k_norm"])
    positions = pos0 + jnp.arange(S, dtype=jnp.int32)
    if cfg.use_rope:
        yarn = cfg.rope_yarn if window is None else None
        q = apply_rope(q, positions, cfg.rope_theta, yarn)
        k = apply_rope(k, positions, cfg.rope_theta, yarn)

    new_cache = None
    if mode == "decode":
        from repro.kernels import ops as kops
        kc = ring_write_step(cache["k"], k[:, 0], pos0)
        vc = ring_write_step(cache["v"], v[:, 0], pos0)
        new_cache = {"k": kc, "v": vc}
        if kops.use_pallas():
            out = kops.decode_attention(
                q[:, 0], kc.transpose(0, 2, 1, 3),
                vc.transpose(0, 2, 1, 3), pos0, window=window)[:, None]
        else:
            kv_pos = slot_positions(pos0, kc.shape[1])
            out = ac.plain_attention(q, kc, vc, q_positions=positions,
                                     kv_positions=kv_pos, causal=True,
                                     window=window)
    else:
        out = ac.attention(q, k, v, q_positions=positions,
                           kv_positions=positions, causal=causal,
                           window=window, q_chunk=cfg.attn_q_chunk,
                           kv_chunk=cfg.attn_kv_chunk,
                           causal_skip=cfg.attn_causal_skip)
        if mode == "prefill":
            C = cache_len if cache_len is not None else S
            new_cache = {"k": ring_from_prefill(k, C),
                         "v": ring_from_prefill(v, C)}
    out = dense(out.reshape(B, S, H * Dh), p["wo"])
    if "bo" in p:
        out = out + p["bo"]
    return out, new_cache


def _apply_mla(cfg: ModelConfig, p, x, *, pos0, mode, cache, window,
               cache_len=None):
    from repro.models.layers import apply_norm  # local import (cycle-free)

    B, S, _ = x.shape
    H = cfg.n_heads
    nope, rope = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    vd, R = cfg.v_head_dim, cfg.kv_lora_rank
    positions = pos0 + jnp.arange(S, dtype=jnp.int32)

    q = _heads(dense(x, p["wq"]), H, nope + rope)
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)

    dkv = x @ p["w_dkv"]                           # (B,S,R+rope)
    ckv = apply_norm(cfg, {"scale": p["kv_norm"]}, dkv[..., :R])
    krope = apply_rope(dkv[..., R:][:, :, None, :], positions,
                       cfg.rope_theta)             # (B,S,1,rope)

    def expand(ckv_seq, krope_seq):
        k_nope = _heads(ckv_seq @ p["w_uk"], H, nope)
        vv = _heads(ckv_seq @ p["w_uv"], H, vd)
        kk = jnp.concatenate(
            [k_nope, jnp.broadcast_to(krope_seq,
                                      k_nope.shape[:-1] + (rope,))], axis=-1)
        return kk, vv

    scale = (nope + rope) ** -0.5
    new_cache = None
    if mode == "decode":
        ckv_c = ring_write_step(cache["ckv"], ckv[:, 0], pos0)
        kr_c = ring_write_step(cache["krope"], krope[:, 0, 0], pos0)
        new_cache = {"ckv": ckv_c, "krope": kr_c}
        kv_pos = slot_positions(pos0, ckv_c.shape[1])
        if cfg.mla_absorb:
            # Weight absorption: attend in the compressed latent space.
            # q_lat = q_nope @ W_uk  (per head), score against cached ckv
            # directly; out = (probs @ ckv) @ W_uv. Avoids re-expanding the
            # whole cache to per-head K/V every decode step.
            w_uk = p["w_uk"].reshape(R, H, nope)
            w_uv = p["w_uv"].reshape(R, H, vd)
            q_lat = jnp.einsum("bqhn,rhn->bqhr", q_nope, w_uk)
            q_cat = jnp.concatenate([q_lat, q_rope], axis=-1)  # (B,1,H,R+rope)
            k_cat = jnp.concatenate(
                [ckv_c, kr_c], axis=-1)[:, :, None, :]         # (B,C,1,R+rope)
            out_lat = ac.plain_attention(
                q_cat, k_cat, ckv_c[:, :, None, :],
                q_positions=positions, kv_positions=kv_pos, causal=True,
                window=window, logit_scale=scale)              # (B,1,H,R)
            out = jnp.einsum("bqhr,rhv->bqhv", out_lat, w_uv)
        else:
            k, v = expand(ckv_c, kr_c[:, :, None, :])
            out = ac.plain_attention(q, k, v, q_positions=positions,
                                     kv_positions=kv_pos, causal=True,
                                     window=window, logit_scale=scale)
    else:
        k, v = expand(ckv, krope)
        out = ac.attention(q, k, v, q_positions=positions,
                           kv_positions=positions, causal=True,
                           window=window, logit_scale=scale,
                           q_chunk=cfg.attn_q_chunk,
                           kv_chunk=cfg.attn_kv_chunk)
        if mode == "prefill":
            C = cache_len if cache_len is not None else S
            new_cache = {"ckv": ring_from_prefill(ckv, C),
                         "krope": ring_from_prefill(krope[:, :, 0, :], C)}
    out = dense(out.reshape(B, S, H * vd), p["wo"])
    return out, new_cache


def apply_cross_attn(cfg: ModelConfig, p, x, *, kv_src=None, cache=None):
    """Cross-attention. kv_src: (B, S_enc, d) encoder/media states, or None
    when a precomputed {"xk","xv"} cache is supplied. Returns (out, cache)."""
    B, S, _ = x.shape
    Dh, H, HK = cfg.resolved_head_dim, cfg.n_heads, cfg.n_kv_heads
    q = dense(x, p["wq"])
    if "bq" in p:
        q = q + p["bq"]
    q = _heads(q, H, Dh)
    if cache is not None and kv_src is None:
        k, v = cache["xk"], cache["xv"]
    else:
        k = _heads(dense(kv_src, p["wk"]), HK, Dh)
        v = dense(kv_src, p["wv"])
        if "bv" in p:
            v = v + p["bv"]
        v = _heads(v, HK, Dh)
        cache = {"xk": k, "xv": v}
    Skv = k.shape[1]
    zero = jnp.zeros((S,), jnp.int32)
    kv_pos = jnp.zeros((Skv,), jnp.int32)
    out = ac.attention(q, k, v, q_positions=zero, kv_positions=kv_pos,
                       causal=False, window=None)
    out = dense(out.reshape(B, S, H * Dh), p["wo"])
    if "bo" in p:
        out = out + p["bo"]
    return out, cache
