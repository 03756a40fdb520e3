"""Kernels: share of quant_matmul's roofline over the traced w8
requests (kernel_costs/quant_matmul.py against the device trace)."""


def read(run):
    return run.roofline("quant_matmul")
