"""Pallas TPU kernels for the compute hot spots, each with a jit'd wrapper
(ops.py) and a pure-jnp oracle (ref.py):

  flash_attention — online-softmax attention, GQA + causal + sliding window
  flash_decode    — single-token ring-cache decode attention (positional mask)
  mamba_scan      — Mamba-1 selective scan, VMEM-resident state tiles
  rglru_scan      — RG-LRU diagonal linear recurrence
  quant_matmul    — int8 x int8 -> int32 matmul with f32 rescale (repro.quant)

On a TPU backend the models run the compiled kernels (ops.use_pallas);
elsewhere they take the pure-jnp reference path, and REPRO_USE_PALLAS=interpret
routes them through the kernels in interpreter mode for the CPU tests.
"""
from repro.kernels.flash_attention import flash_attention
from repro.kernels.flash_decode import flash_decode
from repro.kernels.mamba_scan import mamba_scan
from repro.kernels.quant_matmul import quant_matmul, quant_matmul_ref
from repro.kernels.rglru_scan import rglru_scan
from repro.kernels import ops, ref

__all__ = ["flash_attention", "flash_decode", "mamba_scan", "rglru_scan",
           "quant_matmul", "quant_matmul_ref", "ops", "ref"]
