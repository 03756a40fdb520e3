"""repro.quant: quantized model versions backing the EdgeRL (version, cut)
action space.

``quantize_tree``/``dequantize_tree`` convert dense-projection weights to
``QTensor`` leaves (int8 / int4-packed weight-only, or w8a8); the
``QuantVersion`` registry (bf16 / w8 / w4) derives the env's version-axis
tables (accuracy proxy, FLOP scale, activation + weight bytes) from those
real variants; ``build_version_params`` materializes the per-version param
trees the SplitServingEngine executes. The int8 matmul itself lives in
kernels/quant_matmul.py; models route every dense projection through
models/layers.py::dense, which hands QTensor leaves to
kernels/ops.py::quantized_dense (the kernel dispatch point).
"""
from repro.quant.quantize import (DENSE_WEIGHTS, QTensor, dequantize_tree,
                                  quantize, quantize_act, quantize_tree,
                                  tree_weight_bytes)
from repro.quant.versions import (DEFAULT_VERSIONS, QuantVersion,
                                  accuracy_proxy, build_version_params,
                                  get_version, list_versions,
                                  relative_quant_error)

__all__ = [
    "DENSE_WEIGHTS", "QTensor", "dequantize_tree", "quantize",
    "quantize_act", "quantize_tree", "tree_weight_bytes",
    "DEFAULT_VERSIONS", "QuantVersion", "accuracy_proxy",
    "build_version_params", "get_version", "list_versions",
    "relative_quant_error",
]
