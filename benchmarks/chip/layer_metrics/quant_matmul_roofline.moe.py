"""Kernels, Mellum split serving: share of quant_matmul's roofline over
the traced w8 requests, the attention projections and the output head
(kernel_costs/quant_matmul.py against the device trace)."""


def read(run):
    return run.roofline("quant_matmul")
