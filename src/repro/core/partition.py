"""Cut-point partitioning of the transformer models (head / tail).

The paper splits a CNN at layer l: the device runs M^l (head), ships the
activation, the server runs the tail. For the assigned transformers the cut
sits on a *superblock boundary* (scan granularity), so head/tail execution
slices the stacked layer parameters — jax.tree slicing, no recompilation of
per-layer code.

``split_forward`` == head ∘ tail and must equal the full forward (tested in
tests/test_partition.py). ``cut_points`` enumerates the legal boundaries.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import model as M


def cut_for_layer(cfg: ModelConfig, layer_idx: int) -> Tuple[str, int]:
    """Map a global block index (profile layer numbering) to the nearest
    legal scan-boundary cut (stack_name, scan index).

    Profiles count decoder blocks; stacks scan superblocks that may cover
    several blocks per step (e.g. recurrentgemma's (rec,rec,attn) period),
    so the cut rounds to the closest superblock boundary."""
    remaining = int(layer_idx)
    defs = M.stack_defs(cfg)
    for si, s in enumerate(defs):
        per = sum(sub.repeat for sub in s.subs)
        total = s.length * per
        if remaining <= total or si == len(defs) - 1:
            step = int(round(remaining / per))
            if si == 0:
                step = max(step, 1)   # cut 0 == full offload (caller-level)
            return (s.name, min(step, s.length))
        remaining -= total
    raise AssertionError("unreachable")


def cut_points(cfg: ModelConfig) -> List[Tuple[str, int]]:
    """Legal cut boundaries: (stack_name, index within stack scan)."""
    out = []
    for s in M.stack_defs(cfg):
        for i in range(s.length + 1):
            if (s.name, i) == (M.stack_defs(cfg)[0].name, 0):
                continue  # cut 0 == full offload, handled by caller
            out.append((s.name, i))
    return out


def _slice_stack(p_stack, lo, hi):
    return jax.tree.map(lambda a: a[lo:hi], p_stack)


def _layer0(cfg, stack_name: str) -> int:
    """Decoder layer index of the first layer of stack ``stack_name``."""
    n = 0
    for s in M.stack_defs(cfg):
        if s.name == stack_name:
            return n
        n += s.layers
    raise KeyError(stack_name)


def _run_stacks(cfg, params, x, segments, kv_src):
    aux = jnp.float32(0.0)
    for (sdef, lo, hi) in segments:
        if hi <= lo:
            continue
        sliced = _slice_stack(params["stacks"][sdef.name], lo, hi)
        sub_def = dataclasses.replace(sdef, length=hi - lo)
        per = sdef.layers // sdef.length
        x, _, a = M._apply_stack(cfg, sub_def, sliced, x, mode="train",
                                 pos0=jnp.int32(0), kv_src=kv_src,
                                 layer0=_layer0(cfg, sdef.name) + lo * per)
        aux = aux + a
    return x, aux


def _segments(cfg, cut: Tuple[str, int]):
    """Split stack defs into head segments and tail segments at cut."""
    heads, tails = [], []
    passed = False
    for s in M.stack_defs(cfg):
        if s.name == cut[0]:
            heads.append((s, 0, cut[1]))
            tails.append((s, cut[1], s.length))
            passed = True
        elif not passed:
            heads.append((s, 0, s.length))
        else:
            tails.append((s, 0, s.length))
    return heads, tails


def run_head(cfg: ModelConfig, params, batch, cut: Tuple[str, int]):
    """Device-side: embed + head layers. Returns the cut activation."""
    x = M._embed(cfg, params, batch["tokens"])
    kv = M._kv_src(cfg, params, batch)
    heads, _ = _segments(cfg, cut)
    x, _ = _run_stacks(cfg, params, x, heads, kv)
    return x


def run_tail(cfg: ModelConfig, params, x, batch, cut: Tuple[str, int]):
    """Server-side: tail layers + final norm + logits."""
    kv = M._kv_src(cfg, params, batch)
    _, tails = _segments(cfg, cut)
    x, _ = _run_stacks(cfg, params, x, tails, kv)
    x = M.apply_norm(cfg, params["final_norm"], x)
    return M._head(cfg, params, x)


def split_forward(cfg: ModelConfig, params, batch, cut: Tuple[str, int]):
    """Full split execution; must equal forward_logits(cfg, params, batch)."""
    act = run_head(cfg, params, batch, cut)
    return run_tail(cfg, params, act, batch, cut)


def cut_activation_bytes(cfg: ModelConfig, batch_shape) -> int:
    B, S = batch_shape
    return B * S * cfg.d_model * jnp.dtype(cfg.cdtype).itemsize
