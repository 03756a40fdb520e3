"""Device: share of the traced window with no op on the chip (%)."""


def read(run):
    idle = run.trace and run.trace.get("idle_share")
    return None if idle is None else 100.0 * idle
