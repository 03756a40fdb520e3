"""Mellum split serving: mean host time of one request's infer until its
logits are on the host (queueing excluded), in the traced part."""


def read(run):
    return run.mean("service_ms")
