"""Kernels, Mellum split serving: share of the sliding layers'
flash_attention_window roofline over the traced requests
(kernel_costs/flash_attention_window.py, the window's band of pairs,
against the device trace)."""


def read(run):
    return run.roofline("flash_attention_window")
