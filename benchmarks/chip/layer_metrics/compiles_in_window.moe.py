"""Jit cache, Mellum split serving: XLA compiles inside the measured
window."""


def read(run):
    return run.counters.get("compiles_in_window")
