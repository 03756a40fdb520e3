"""Controller: mean host time of one slot's decide (the jitted
A2C actor, until its actions are on the host), in the traced part."""


def read(run):
    return run.mean("decide_ms")
