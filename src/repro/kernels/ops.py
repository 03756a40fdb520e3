"""jit'd wrappers dispatching between Pallas kernels and jnp references.

The kernel path is chosen by platform (``use_pallas()``): on a TPU backend
every dispatch runs the compiled Pallas kernel. Elsewhere the models take
the pure-jnp reference path, unless REPRO_USE_PALLAS=interpret routes them
through the kernels in interpreter mode (the CPU tests' switch).
``jnp_reference()`` forces the reference path on any backend, so the
kernel path can be checked against it on the chip.

The choice is made while a function is traced, and jit caches do not key
on it: trace a fresh ``jax.jit`` under ``jnp_reference()``.
"""
from __future__ import annotations

import contextlib
import contextvars
import os
import re
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.flash_decode import flash_decode as _flash_decode
from repro.kernels.mamba_scan import mamba_scan as _mamba_scan
from repro.kernels.quant_matmul import quant_matmul as _quant_matmul
from repro.kernels.quant_matmul import quant_matmul_ref as _quant_matmul_ref
from repro.kernels.rglru_scan import rglru_scan as _rglru_scan
from repro.kernels import ref
from repro.quant.quantize import QTensor, quantize_act


_FORCE_REFERENCE = contextvars.ContextVar("force_reference", default=False)


@contextlib.contextmanager
def jnp_reference():
    """Trace the models on the jnp reference path, whatever the backend."""
    token = _FORCE_REFERENCE.set(True)
    try:
        yield
    finally:
        _FORCE_REFERENCE.reset(token)


def use_pallas() -> Optional[str]:
    """"tpu" (compiled kernels), "interpret" (CPU tests) or None (jnp)."""
    if _FORCE_REFERENCE.get():
        return None
    if jax.default_backend() == "tpu":
        return "tpu"
    if os.environ.get("REPRO_USE_PALLAS", "").lower() == "interpret":
        return "interpret"
    return None


_TPU_CUSTOM_CALL = re.compile(
    r'%([\w.\-]+) = [^\n]*custom_call_target="tpu_custom_call"')


def compiled_kernels(compiled) -> set:
    """Names of the Pallas kernels in a compiled TPU program (each
    kernel's instruction is named after its jitted wrapper)."""
    return {m.rsplit(".", 1)[0]
            for m in _TPU_CUSTOM_CALL.findall(compiled.as_text())}


def quantized_dense(x, w: QTensor):
    """Dense projection against a quantized weight leaf.

    Weight-only leaves (w8 / packed w4) dequantize to f32 and use the
    plain matmul; w8a8 leaves quantize the activations per row and run the
    int8 x int8 -> int32 path — the Pallas kernel where ``use_pallas()``
    says so, the jnp oracle otherwise. models/layers.py::dense routes every
    dense projection here, so a quantized param tree changes no model code.
    """
    if w.act_bits == 8 and w.bits == 8:
        xq, xs = quantize_act(x)
        lead = x.shape[:-1]
        xq2 = xq.reshape(-1, x.shape[-1])
        xs2 = xs.reshape(-1)
        ws = w.scale.reshape(-1)
        mode = use_pallas()
        if mode:
            out = _quant_matmul(xq2, w.q, xs2, ws,
                                interpret=(mode == "interpret"))
        else:
            out = _quant_matmul_ref(xq2, w.q, xs2, ws)
        return out.reshape(*lead, -1).astype(x.dtype)
    return x @ w.dequantize().astype(x.dtype)


def attention_bhsd(q, k, v, *, causal=True, window=None, logit_scale=None):
    """(B,H,S,D) attention via flash kernel or oracle."""
    mode = use_pallas()
    if mode:
        return _flash(q, k, v, causal=causal, window=window,
                      logit_scale=logit_scale,
                      interpret=(mode == "interpret"))
    return ref.flash_attention_ref(q, k, v, causal=causal, window=window,
                                   logit_scale=logit_scale)


def mamba_scan_full(cfg, p, u, dt, Bm, Cm):
    """Selective scan incl. D-skip. u/dt: (B,S,DI); Bm/Cm: (B,S,N)."""
    A = -jnp.exp(p["a_log"].astype(jnp.float32))
    mode = use_pallas()
    if mode:
        y, h = _mamba_scan(u.astype(jnp.float32), dt, Bm, Cm, A,
                           interpret=(mode == "interpret"))
        y = y + u.astype(jnp.float32) * p["d_skip"][None, None]
        return y.astype(u.dtype), h
    from repro.models.ssm import ssm_scan_chunked
    return ssm_scan_chunked(cfg, p, u)


def rglru_scan_full(a, gx):
    """Diagonal recurrence. a/gx: (B,S,W) f32 -> (h_seq, h_last)."""
    mode = use_pallas()
    if mode:
        return _rglru_scan(a, gx, interpret=(mode == "interpret"))
    return ref.rglru_scan_ref(a, gx)


def decode_attention(q_bhd, k_cache, v_cache, pos, *, window=None,
                     logit_scale=None):
    """Single-token ring-cache attention. q: (B,H,Dh); caches (B,HK,C,Dh)."""
    mode = use_pallas()
    if mode:
        return _flash_decode(q_bhd, k_cache, v_cache, pos, window=window,
                             logit_scale=logit_scale,
                             interpret=(mode == "interpret"))
    from repro.models.attention import slot_positions
    from repro.models.attention_core import plain_attention
    C = k_cache.shape[2]
    kv_pos = slot_positions(jnp.asarray(pos, jnp.int32), C)
    out = plain_attention(
        q_bhd[:, None], k_cache.transpose(0, 2, 1, 3),
        v_cache.transpose(0, 2, 1, 3),
        q_positions=jnp.asarray(pos, jnp.int32).reshape(1),
        kv_positions=kv_pos, causal=True, window=window,
        logit_scale=logit_scale)
    return out[:, 0]
