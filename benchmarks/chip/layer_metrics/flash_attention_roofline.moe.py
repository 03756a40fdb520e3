"""Kernels, Mellum split serving: share of the full layers'
flash_attention roofline over the traced requests
(kernel_costs/flash_attention.py against the device trace)."""


def read(run):
    return run.roofline("flash_attention")
