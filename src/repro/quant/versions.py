"""QuantVersion registry: the executable model-version axis of the EdgeRL
action space.

The paper's "DNN version j" is a compression-derived variant trading
accuracy for compute/bytes (VGG11 vs VGG19). Here a version is a
quantization level of the same trunk:

  bf16 — full-precision baseline (no quantization)
  w8   — w8a8: int8 weights + dynamic int8 activations; runs on the MXU's
         int8 path (2x MAC throughput) and ships int8 cut activations
  w4   — int4-packed weight-only: 4x smaller weights, full-precision math

Everything the env's ProfileTables needs per version is *derived* here
instead of hand-tuned: the accuracy proxy from measured quantization error
on a probe layer, the FLOP cost scale from the int8 MXU speedup, activation
itemsize from the shipped dtype, and weight bytes from the code width.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Optional, Sequence

import jax
import jax.numpy as jnp

from repro.quant.quantize import quantize, quantize_act, quantize_trees


@dataclasses.dataclass(frozen=True)
class QuantVersion:
    name: str
    weight_bits: int = 16
    act_bits: int = 0           # 0 = activations stay in compute dtype

    @property
    def mode(self) -> Optional[str]:
        """quantize_tree mode; None = leave params untouched."""
        if self.weight_bits >= 16:
            return None
        if self.weight_bits == 4:
            return "w4"
        return "w8a8" if self.act_bits == 8 else "w8wo"

    @property
    def bytes_per_param(self) -> float:
        """Nominal wire width of the quantized weight codes, per param.

        Only meaningful for quantized versions: profiles price
        full-precision leaves (and the whole tree when mode is None) at
        the config's actual param dtype width (cfg.pdtype.itemsize), not
        this number — see profiles.build_quant_versions."""
        return self.weight_bits / 8.0

    @property
    def act_itemsize(self) -> int:
        """Nominal link width of the cut activation: 1 for int8-shipping
        versions. The 2 for full-precision versions assumes the TPU bf16
        serving regime; profiles override it with the config's actual
        compute dtype width (cfg.cdtype.itemsize)."""
        return 1 if self.act_bits == 8 else 2

    @property
    def matmul_cost_scale(self) -> float:
        """Effective FLOP cost multiplier: int8 x int8 runs at 2x MXU
        throughput, so a w8a8 MAC costs half a bf16 MAC."""
        return 0.5 if (self.weight_bits <= 8 and self.act_bits == 8) else 1.0


_REGISTRY: Dict[str, QuantVersion] = {
    "bf16": QuantVersion("bf16", weight_bits=16, act_bits=0),
    "w8": QuantVersion("w8", weight_bits=8, act_bits=8),
    "w4": QuantVersion("w4", weight_bits=4, act_bits=0),
}

DEFAULT_VERSIONS = ("bf16", "w8", "w4")


def get_version(name: str) -> QuantVersion:
    if name not in _REGISTRY:
        raise KeyError(
            f"unknown quant version {name!r}; known: {sorted(_REGISTRY)}")
    return _REGISTRY[name]


def list_versions() -> Dict[str, QuantVersion]:
    return dict(_REGISTRY)


@functools.lru_cache(maxsize=None)
def relative_quant_error(weight_bits: int, act_bits: int, *, d: int = 512,
                         f: int = 512, rows: int = 32,
                         seed: int = 0) -> float:
    """Measured relative output error of one quantized dense projection.

    Probe: a fan-in-scaled gaussian weight (the init distribution of every
    dense projection in models/) against gaussian activations. This is the
    *measured* accuracy cost of a version — profiles derive their accuracy
    proxy from it instead of a hand-tuned constant.
    """
    qv = QuantVersion("probe", weight_bits, act_bits)
    if qv.mode is None:
        return 0.0
    k1, k2 = jax.random.split(jax.random.key(seed))
    w = jax.random.normal(k1, (d, f), jnp.float32) * d ** -0.5
    x = jax.random.normal(k2, (rows, d), jnp.float32)
    y = x @ w
    qt = quantize(w, qv.mode)
    if qv.act_bits == 8:
        from repro.kernels.quant_matmul import quant_matmul_ref
        xq, xs = quantize_act(x)
        yq = quant_matmul_ref(xq, qt.q, xs.reshape(-1), qt.scale.reshape(-1))
    else:
        yq = x @ qt.dequantize()
    num = jnp.linalg.norm(y - yq)
    den = jnp.maximum(jnp.linalg.norm(y), 1e-12)
    return float(num / den)


def accuracy_proxy(qv: QuantVersion, base_acc: float = 0.75,
                   dense_frac: float = 1.0) -> float:
    """Version accuracy for the env tables: baseline accuracy degraded by
    the measured per-layer quantization error (SNR-proportional proxy).

    ``dense_frac`` is the fraction of the model's compute that actually
    runs through quantized dense projections (profiles pass the
    dense-share / total-FLOPs ratio) — an SSM trunk whose mixers stay
    full precision must not be charged the full dense-probe error."""
    err = relative_quant_error(qv.weight_bits, qv.act_bits) * dense_frac
    return base_acc * (1.0 - err)


def build_version_params(cfg, params,
                         versions: Sequence[str] = DEFAULT_VERSIONS) -> Dict:
    """Materialize the param tree of each requested version.

    Returns {version_name: params} where bf16 aliases the input tree and
    each quantized version holds fresh QTensor leaves for the weights
    ``quantize_tree`` converts and shares every other leaf with the input
    tree (``quantize_trees``: one jitted call over those weights alone).
    """
    qvs = {name: get_version(name) for name in versions}
    trees = quantize_trees(params, tuple(dict.fromkeys(
        qv.mode for qv in qvs.values() if qv.mode is not None)))
    return {name: params if qv.mode is None else trees[qv.mode]
            for name, qv in qvs.items()}
