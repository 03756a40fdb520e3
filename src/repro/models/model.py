"""Model assembly: stacks of blocks scanned with ``lax.scan`` over stacked
layer parameters (keeps HLO size O(1) in depth — required for the 100-layer
VLM / 64-layer SSM dry-runs).

Public API:
  plan_model(cfg)                 -> param plan (shapes + logical axes)
  init(cfg, rng)                  -> params
  forward_train(cfg, params, batch, remat) -> (loss, metrics)
  prefill(cfg, params, batch, total_len)   -> (last_logits, cache)
  init_cache(cfg, batch_size, seq_len)     -> zeroed decode cache
  decode_step(cfg, params, cache, token, pos) -> (logits, new_cache)

Stacks: homogeneous runs of layers grouped into scan-able "superblocks"
(e.g. recurrentgemma (rec,rec,attn) periods; llama-vision 4x self + 1 cross).
A stack whose layers mix full and sliding-window attention (``layer_types``)
stays one scan of single layers, each choosing its type from a flag it is
scanned with, so that every layer boundary is a scan boundary.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import params as pp
from repro.models.params import P
from repro.models.layers import (plan_norm, apply_norm, dense,
                                 sinusoidal_positions)
from repro.quant.quantize import QTensor
from repro.models.blocks import plan_block, apply_block


@dataclasses.dataclass(frozen=True)
class Sub:
    name: str
    kind: str
    repeat: int = 1
    moe: bool = False


@dataclasses.dataclass(frozen=True)
class StackDef:
    name: str
    length: int          # scan length
    subs: Tuple[Sub, ...]

    @property
    def layers(self) -> int:
        return self.length * sum(s.repeat for s in self.subs)


def stack_defs(cfg: ModelConfig) -> Tuple[StackDef, ...]:
    """Decoder trunk stacks, in execution order."""
    L = cfg.n_layers
    if cfg.ssm:
        return (StackDef("main", L, (Sub("blk", "ssm"),)),)
    if cfg.block_pattern:
        p = cfg.block_pattern
        n_per, n_full = len(p), L // len(p)
        defs = [StackDef("period", n_full,
                         tuple(Sub(f"s{i}", p[i]) for i in range(n_per)))]
        rem = L - n_full * n_per
        if rem:
            rem_kinds = p[:rem]
            if len(set(rem_kinds)) == 1:
                defs.append(StackDef("tail", rem, (Sub("blk", rem_kinds[0]),)))
            else:
                for i, k in enumerate(rem_kinds):
                    defs.append(StackDef(f"tail{i}", 1, (Sub("blk", k),)))
        return tuple(defs)
    if cfg.cross_attn_every:
        e = cfg.cross_attn_every
        n_full = L // e
        defs = [StackDef("period", n_full,
                         (Sub("attn", "attn", e - 1), Sub("xattn", "xattn")))]
        rem = L - n_full * e
        if rem:
            defs.append(StackDef("tail", rem, (Sub("blk", "attn"),)))
        return tuple(defs)
    if cfg.enc_dec:
        return (StackDef("main", L, (Sub("blk", "dec"),)),)
    if cfg.moe:
        defs = []
        if cfg.first_dense_layers:
            defs.append(StackDef("dense0", cfg.first_dense_layers,
                                 (Sub("blk", "attn", 1, False),)))
        defs.append(StackDef("main", L - cfg.first_dense_layers,
                             (Sub("blk", "attn", 1, True),)))
        return tuple(defs)
    return (StackDef("main", L, (Sub("blk", "attn"),)),)


def enc_stack_defs(cfg: ModelConfig) -> Tuple[StackDef, ...]:
    if not cfg.enc_dec:
        return ()
    return (StackDef("enc", cfg.n_encoder_layers, (Sub("blk", "enc"),)),)


def _sub_window(cfg: ModelConfig, sub: Sub) -> Optional[int]:
    if sub.kind == "attn" and cfg.block_pattern:
        return cfg.local_window          # griffin local attention
    if sub.kind == "attn" and cfg.layer_types:
        # a mixed stack picks per layer (_full_flags); else all alike
        return None if cfg.mixed_attention else cfg.attention_windows()[0]
    if sub.kind in ("attn", "dec"):
        return cfg.sliding_window
    return None


def _full_flags(cfg: ModelConfig, layer0: int, length: int):
    """Per scan step of a mixed stack starting at decoder layer
    ``layer0``: whether the layer attends in full (else in its window)."""
    if not cfg.mixed_attention:
        return None
    wins = cfg.attention_windows()[layer0:layer0 + length]
    return jnp.asarray([w is None for w in wins])


# --------------------------------------------------------------------------
# plans / init
# --------------------------------------------------------------------------

def _plan_superblock(cfg: ModelConfig, sdef: StackDef):
    plan = {}
    for sub in sdef.subs:
        bp = plan_block(cfg, sub.kind, moe=sub.moe)
        plan[sub.name] = pp.stack(bp, sub.repeat) if sub.repeat > 1 else bp
    return plan


def plan_model(cfg: ModelConfig):
    d, V = cfg.d_model, cfg.vocab_size
    plan = {
        "tok_embed": P((V, d), ("vocab", "embed"), "normal", scale=0.01),
        "final_norm": plan_norm(cfg),
        "stacks": {s.name: pp.stack(_plan_superblock(cfg, s), s.length)
                   for s in stack_defs(cfg)},
    }
    if not cfg.tie_embeddings:
        plan["lm_head"] = P((d, V), ("embed", "vocab"))
    if cfg.enc_dec:
        plan["enc_stacks"] = {
            s.name: pp.stack(_plan_superblock(cfg, s), s.length)
            for s in enc_stack_defs(cfg)}
        plan["enc_norm"] = plan_norm(cfg)
    return plan


def init(cfg: ModelConfig, rng: jax.Array):
    return pp.materialize(plan_model(cfg), rng, cfg.pdtype)


def abstract_params(cfg: ModelConfig):
    return pp.abstract(plan_model(cfg), cfg.pdtype)


def param_axes(cfg: ModelConfig):
    return pp.axes_tree(plan_model(cfg))


def n_params(cfg: ModelConfig) -> int:
    return pp.count_params(plan_model(cfg))


# --------------------------------------------------------------------------
# stack application
# --------------------------------------------------------------------------

def _apply_stack(cfg: ModelConfig, sdef: StackDef, p_stack, x, *, mode: str,
                 pos0, cache=None, kv_src=None, total_len=None, remat=False,
                 layer0: int = 0):
    """Scan the stack's superblocks over x. ``layer0``: the decoder layer
    index of the stack's first layer (a mixed stack's types follow it)."""
    want_cache = mode in ("prefill", "decode")
    has_cache_in = cache is not None
    flags = _full_flags(cfg, layer0, sdef.length)

    def body(carry, xs):
        x, aux = carry
        if flags is not None:
            xs, full = xs
        else:
            full = None
        p_sl, c_sl = xs if has_cache_in else (xs, None)
        new_c = {}
        for sub in sdef.subs:
            window = _sub_window(cfg, sub)
            clen = None
            if mode == "prefill" and total_len is not None:
                clen = min(total_len, window) if window else total_len
            sub_cache = c_sl[sub.name] if c_sl is not None else None
            if sub.repeat == 1:
                x, nc, a = apply_block(cfg, sub.kind, p_sl[sub.name], x,
                                       mode=mode, pos0=pos0, cache=sub_cache,
                                       kv_src=kv_src, window=window,
                                       cache_len=clen, full=full)
                aux = aux + a
                if want_cache:
                    new_c[sub.name] = nc
            else:
                def inner(carry2, xs2, _k=sub.kind, _w=window, _cl=clen):
                    x2, aux2 = carry2
                    pp2, cc2 = xs2 if has_cache_in else (xs2, None)
                    x2, nc2, a2 = apply_block(cfg, _k, pp2, x2, mode=mode,
                                              pos0=pos0, cache=cc2,
                                              kv_src=kv_src, window=_w,
                                              cache_len=_cl)
                    return (x2, aux2 + a2), (nc2 if want_cache else None)

                inner_xs = ((p_sl[sub.name], sub_cache) if has_cache_in
                            else p_sl[sub.name])
                (x, aux), ncs = jax.lax.scan(inner, (x, aux), inner_xs)
                if want_cache:
                    new_c[sub.name] = ncs
        return (x, aux), (new_c if want_cache else None)

    if remat and mode == "train":
        body = jax.checkpoint(body)
    xs = (p_stack, cache) if has_cache_in else p_stack
    if flags is not None:
        xs = (xs, flags)
    (x, aux), new_cache = jax.lax.scan(body, (x, jnp.float32(0.0)), xs)
    return x, new_cache, aux


def _embed(cfg: ModelConfig, params, tokens, pos0=0):
    x = jnp.take(params["tok_embed"], tokens, axis=0).astype(cfg.cdtype)
    if cfg.family == "hybrid":   # gemma convention
        x = x * jnp.asarray(cfg.d_model ** 0.5, cfg.cdtype)
    if cfg.enc_dec:              # whisper decoder: absolute positions, no RoPE
        x = x + sinusoidal_positions(tokens.shape[1], cfg.d_model,
                                     offset=pos0).astype(cfg.cdtype)
    return x


def _encode(cfg: ModelConfig, params, enc_frames):
    x = enc_frames.astype(cfg.cdtype)
    x = x + sinusoidal_positions(x.shape[1], cfg.d_model).astype(cfg.cdtype)
    for s in enc_stack_defs(cfg):
        x, _, _ = _apply_stack(cfg, s, params["enc_stacks"][s.name], x,
                               mode="train", pos0=jnp.int32(0))
    return apply_norm(cfg, params["enc_norm"], x)


def _trunk(cfg: ModelConfig, params, x, *, mode, pos0, caches=None,
           kv_src=None, total_len=None, remat=False):
    aux_total = jnp.float32(0.0)
    new_caches = {} if mode in ("prefill", "decode") else None
    layer0 = 0
    for s in stack_defs(cfg):
        cache_s = caches[s.name] if caches is not None else None
        x, nc, aux = _apply_stack(cfg, s, params["stacks"][s.name], x,
                                  mode=mode, pos0=pos0, cache=cache_s,
                                  kv_src=kv_src, total_len=total_len,
                                  remat=remat, layer0=layer0)
        layer0 += s.layers
        aux_total = aux_total + aux
        if new_caches is not None:
            new_caches[s.name] = nc
    return apply_norm(cfg, params["final_norm"], x), new_caches, aux_total


def _head(cfg: ModelConfig, params, h):
    if cfg.tie_embeddings:
        return jnp.einsum("...d,vd->...v", h,
                          params["tok_embed"].astype(h.dtype))
    w = params["lm_head"]
    if not isinstance(w, QTensor):
        w = w.astype(h.dtype)
    return dense(h, w)


# --------------------------------------------------------------------------
# public entry points
# --------------------------------------------------------------------------

def _kv_src(cfg: ModelConfig, params, batch):
    if cfg.enc_dec:
        return _encode(cfg, params, batch["enc_frames"])
    if cfg.cross_attn_every:
        return batch["media"].astype(cfg.cdtype)
    return None


LOSS_CHUNK = 512


def lm_loss(cfg: ModelConfig, params, h, targets):
    """Chunked cross-entropy. h: (B,S,d); targets: (B,S) int32, <0 = masked."""
    B, S, d = h.shape
    chunk = min(LOSS_CHUNK, S)
    if S % chunk != 0:
        chunk = S
    n = S // chunk
    hs = h.reshape(B, n, chunk, d).transpose(1, 0, 2, 3)
    ts = targets.reshape(B, n, chunk).transpose(1, 0, 2)

    def body(acc, xs):
        hc, tc = xs
        logits = _head(cfg, params, hc).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(
            logits, jnp.maximum(tc, 0)[..., None], axis=-1)[..., 0]
        mask = (tc >= 0).astype(jnp.float32)
        nll = (lse - tgt) * mask
        loss_sum, count = acc
        return (loss_sum + jnp.sum(nll), count + jnp.sum(mask)), None

    (loss_sum, count), _ = jax.lax.scan(
        body, (jnp.float32(0.0), jnp.float32(0.0)), (hs, ts))
    return loss_sum / jnp.maximum(count, 1.0)


def forward_train(cfg: ModelConfig, params, batch, remat: bool = True):
    """batch: tokens (B,S), targets (B,S), [media | enc_frames]."""
    x = _embed(cfg, params, batch["tokens"])
    kv = _kv_src(cfg, params, batch)
    h, _, aux = _trunk(cfg, params, x, mode="train", pos0=jnp.int32(0),
                       kv_src=kv, remat=remat)
    loss = lm_loss(cfg, params, h, batch["targets"])
    return loss + aux, {"lm_loss": loss, "aux_loss": aux}


def forward_logits(cfg: ModelConfig, params, batch):
    """Full-sequence logits (small-model testing path)."""
    x = _embed(cfg, params, batch["tokens"])
    kv = _kv_src(cfg, params, batch)
    h, _, _ = _trunk(cfg, params, x, mode="train", pos0=jnp.int32(0), kv_src=kv)
    return _head(cfg, params, h)


def prefill(cfg: ModelConfig, params, batch, total_len: Optional[int] = None):
    tokens = batch["tokens"]
    x = _embed(cfg, params, tokens)
    kv = _kv_src(cfg, params, batch)
    total = total_len if total_len is not None else tokens.shape[1]
    h, caches, _ = _trunk(cfg, params, x, mode="prefill", pos0=jnp.int32(0),
                          kv_src=kv, total_len=total)
    logits = _head(cfg, params, h[:, -1])
    return logits, caches


def decode_step(cfg: ModelConfig, params, caches, token, pos):
    """token: (B,) int32; pos: scalar int32 (tokens already cached)."""
    x = _embed(cfg, params, token[:, None], pos0=pos)
    h, new_caches, _ = _trunk(cfg, params, x, mode="decode",
                              pos0=jnp.asarray(pos, jnp.int32), caches=caches)
    return _head(cfg, params, h[:, 0]), new_caches


# --------------------------------------------------------------------------
# cache construction (decode entry without a real prefill — dry-run path)
# --------------------------------------------------------------------------

def _zero_cache_block(cfg: ModelConfig, kind: str, window, B: int,
                      seq_len: int, dtype):
    Dh, HK = cfg.resolved_head_dim, cfg.n_kv_heads
    C = min(window, seq_len) if window else seq_len
    if kind == "ssm":
        return {"conv": jnp.zeros((B, cfg.ssm_conv - 1, cfg.d_inner), dtype),
                "ssm": jnp.zeros((B, cfg.d_inner, cfg.ssm_state), dtype)}
    if kind == "rec":
        w = cfg.resolved_lru_width
        return {"conv": jnp.zeros((B, cfg.ssm_conv - 1, w), dtype),
                "lru": jnp.zeros((B, w), dtype)}
    if kind == "xattn":
        return {"xk": jnp.zeros((B, cfg.n_media_tokens, HK, Dh), dtype),
                "xv": jnp.zeros((B, cfg.n_media_tokens, HK, Dh), dtype)}
    if cfg.use_mla and kind in ("attn",):
        return {"ckv": jnp.zeros((B, C, cfg.kv_lora_rank), dtype),
                "krope": jnp.zeros((B, C, cfg.qk_rope_head_dim), dtype)}
    blk = {"k": jnp.zeros((B, C, HK, Dh), dtype),
           "v": jnp.zeros((B, C, HK, Dh), dtype)}
    if kind == "dec":
        blk["xk"] = jnp.zeros((B, cfg.encoder_seq, HK, Dh), dtype)
        blk["xv"] = jnp.zeros((B, cfg.encoder_seq, HK, Dh), dtype)
    return blk


def init_cache(cfg: ModelConfig, B: int, seq_len: int, dtype=None):
    dtype = dtype if dtype is not None else cfg.cdtype

    def tile(tree, reps):
        return jax.tree.map(
            lambda a: jnp.broadcast_to(a, reps + a.shape)
            if reps else a, tree)

    caches = {}
    for s in stack_defs(cfg):
        sub_caches = {}
        for sub in s.subs:
            window = _sub_window(cfg, sub)
            blk = _zero_cache_block(cfg, sub.kind, window, B, seq_len, dtype)
            reps = (s.length,) if sub.repeat == 1 else (s.length, sub.repeat)
            sub_caches[sub.name] = tile(blk, reps)
        caches[s.name] = sub_caches
    return caches


# --------------------------------------------------------------------------
# logical axes for cache trees (mirrors init_cache; used by launch/shardings)
# --------------------------------------------------------------------------

def _cache_axes_block(cfg: ModelConfig, kind: str):
    if kind == "ssm":
        return {"conv": ("batch", None, "inner"),
                "ssm": ("batch", "inner", None)}
    if kind == "rec":
        return {"conv": ("batch", None, "lru"), "lru": ("batch", "lru")}
    if kind == "xattn":
        return {"xk": ("batch", None, "kv_heads", None),
                "xv": ("batch", None, "kv_heads", None)}
    if cfg.use_mla and kind == "attn":
        return {"ckv": ("batch", "kv_cache_seq", None),
                "krope": ("batch", "kv_cache_seq", None)}
    blk = {"k": ("batch", "kv_cache_seq", "kv_heads", None),
           "v": ("batch", "kv_cache_seq", "kv_heads", None)}
    if kind == "dec":
        blk["xk"] = ("batch", None, "kv_heads", None)
        blk["xv"] = ("batch", None, "kv_heads", None)
    return blk


def cache_axes(cfg: ModelConfig):
    axes = {}
    for s in stack_defs(cfg):
        sub_axes = {}
        for sub in s.subs:
            blk = _cache_axes_block(cfg, sub.kind)
            lead = ("layers",) if sub.repeat == 1 else ("layers", "layers")
            sub_axes[sub.name] = {k: lead + v for k, v in blk.items()}
        axes[s.name] = sub_axes
    return axes
