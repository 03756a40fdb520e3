"""Model step, Mellum split serving: forward operations of the traced
requests (mellum_cost.py, with each request's routed rows) over the
device busy time inside their infer spans, as a share of the bf16 peak."""


def read(run):
    return run.mfu()
