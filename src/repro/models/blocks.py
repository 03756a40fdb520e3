"""Transformer blocks: one plan/apply pair per block kind.

Kinds:
  attn  — pre-norm self-attention + pre-norm MLP (or MoE) [dense/moe/griffin-local]
  enc   — bidirectional self-attention + MLP (whisper encoder)
  dec   — self-attention + cross-attention + MLP (whisper decoder)
  xattn — gated cross-attention + gated MLP (llama-3.2-vision image layers)
  ssm   — mamba mixer (norm + mixer only)
  rec   — RG-LRU recurrent mixer + MLP (griffin)

Each apply returns (x, new_cache, aux_loss).
"""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.params import P
from repro.models.layers import plan_norm, apply_norm, plan_mlp, apply_mlp
from repro.models.attention import (plan_self_attn, apply_self_attn,
                                    plan_cross_attn, apply_cross_attn)
from repro.models.moe import plan_moe, apply_moe
from repro.models.ssm import plan_ssm, apply_ssm
from repro.models.rglru import plan_rec, apply_rec

ZERO = jnp.float32(0.0)


def plan_block(cfg: ModelConfig, kind: str, moe: bool = False):
    bias = cfg.attn_bias  # whisper-style mlp biases ride along with attn bias
    if kind == "ssm":
        return {"norm": plan_norm(cfg), "ssm": plan_ssm(cfg)}
    if kind == "rec":
        return {"norm1": plan_norm(cfg), "rec": plan_rec(cfg),
                "norm2": plan_norm(cfg), "mlp": plan_mlp(cfg)}
    if kind in ("attn", "enc"):
        plan = {"norm1": plan_norm(cfg), "attn": plan_self_attn(cfg),
                "norm2": plan_norm(cfg)}
        if moe:
            plan["moe"] = plan_moe(cfg)
        else:
            plan["mlp"] = plan_mlp(cfg, bias=bias)
        return plan
    if kind == "dec":
        return {"norm1": plan_norm(cfg), "attn": plan_self_attn(cfg),
                "norm2": plan_norm(cfg), "xattn": plan_cross_attn(cfg),
                "norm3": plan_norm(cfg), "mlp": plan_mlp(cfg, bias=bias)}
    if kind == "xattn":
        return {"norm1": plan_norm(cfg), "xattn": plan_cross_attn(cfg),
                "gate_attn": P((1,), (None,), "zeros", dtype="float32"),
                "norm2": plan_norm(cfg), "mlp": plan_mlp(cfg),
                "gate_mlp": P((1,), (None,), "zeros", dtype="float32")}
    raise ValueError(f"unknown block kind {kind!r}")


def apply_block(cfg: ModelConfig, kind: str, p, x, *, mode: str, pos0,
                cache=None, kv_src=None, window: Optional[int] = None,
                cache_len: Optional[int] = None, full=None):
    if kind == "ssm":
        h, nc = apply_ssm(cfg, p["ssm"], apply_norm(cfg, p["norm"], x),
                          mode=mode, cache=cache)
        return x + h, nc, ZERO

    if kind == "rec":
        h, nc = apply_rec(cfg, p["rec"], apply_norm(cfg, p["norm1"], x),
                          mode=mode, cache=cache)
        x = x + h
        x = x + apply_mlp(cfg, p["mlp"], apply_norm(cfg, p["norm2"], x))
        return x, nc, ZERO

    if kind in ("attn", "enc"):
        causal = kind == "attn"
        h, nc = apply_self_attn(cfg, p["attn"], apply_norm(cfg, p["norm1"], x),
                                pos0=pos0, mode=mode, cache=cache,
                                window=window, causal=causal,
                                cache_len=cache_len, full=full)
        x = x + h
        y = apply_norm(cfg, p["norm2"], x)
        if "moe" in p:
            m, aux = apply_moe(cfg, p["moe"], y)
            return x + m, nc, aux
        return x + apply_mlp(cfg, p["mlp"], y), nc, ZERO

    if kind == "dec":
        self_cache = None if cache is None else {"k": cache["k"], "v": cache["v"]}
        h, nc_self = apply_self_attn(cfg, p["attn"],
                                     apply_norm(cfg, p["norm1"], x),
                                     pos0=pos0, mode=mode, cache=self_cache,
                                     window=window, causal=True,
                                     cache_len=cache_len)
        x = x + h
        cross_cache = None
        if cache is not None and "xk" in cache:
            cross_cache = {"xk": cache["xk"], "xv": cache["xv"]}
        h, nc_cross = apply_cross_attn(cfg, p["xattn"],
                                       apply_norm(cfg, p["norm2"], x),
                                       kv_src=kv_src, cache=cross_cache)
        x = x + h
        x = x + apply_mlp(cfg, p["mlp"], apply_norm(cfg, p["norm3"], x))
        nc = None
        if mode in ("prefill", "decode"):
            nc = dict(nc_self or {})
            nc.update(nc_cross or {})
        return x, nc, ZERO

    if kind == "xattn":
        cross_cache = cache if (cache is not None and "xk" in cache) else None
        h, nc = apply_cross_attn(cfg, p["xattn"],
                                 apply_norm(cfg, p["norm1"], x),
                                 kv_src=kv_src, cache=cross_cache)
        x = x + jnp.tanh(p["gate_attn"]).astype(x.dtype) * h
        h = apply_mlp(cfg, p["mlp"], apply_norm(cfg, p["norm2"], x))
        x = x + jnp.tanh(p["gate_mlp"]).astype(x.dtype) * h
        new_cache = nc if mode in ("prefill", "decode") else None
        return x, new_cache, ZERO

    raise ValueError(f"unknown block kind {kind!r}")
