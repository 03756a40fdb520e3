"""Kernels: share of flash_attention's roofline in the traced
requests (kernel_costs/flash_attention.py against the device trace)."""


def read(run):
    return run.roofline("flash_attention")
