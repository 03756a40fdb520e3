"""Model step: forward operations of the traced requests over the
device busy time inside their infer spans, as a share of the bf16 peak."""


def read(run):
    return run.mfu()
