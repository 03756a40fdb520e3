"""Kernels, Mellum split serving: share of grouped_matmul's roofline (the
held experts' FFNs) over the traced requests: kernel_costs/grouped_matmul.py
at the rows the reference routes each request, against the device trace."""


def read(run):
    return run.roofline("grouped_matmul")
