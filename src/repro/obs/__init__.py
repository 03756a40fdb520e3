"""repro.obs — structured tracing, metrics and JAX retrace accounting.

One process-global recorder (null by default — zero overhead when off)
behind module-level hooks:

    from repro import obs

    with obs.recording("events.jsonl") as rec:      # enable
        with obs.span("fleet.epoch", epoch=0):       # nested timed span
            obs.event("drift.regime_switch", regime=1)
            obs.inc("fleet.dropped", 3, policy="a2c")  # labeled counter
    # -> versioned JSONL; fold with scripts/obsview.py or obs.report

JAX accounting (``obs.jaxmon``) counts jit re-traces per call site and
compile wall-time process-wide; ``obs.log``/``info``/``debug``/``warn``
is the structured console logger (verbosity-gated print + recorded log
events). See DESIGN.md §9 for the architecture and the rules
(recording never changes results; no host callbacks on traced paths).
"""
from repro.obs import jaxmon, report
from repro.obs.events import (SCHEMA_VERSION, NullRecorder, Recorder,
                              debug, event, get_recorder, get_verbosity,
                              info, log, read_events, recording,
                              set_recorder, set_verbosity, span, warn)
from repro.obs.metrics import Metrics, inc, observe

__all__ = [
    "SCHEMA_VERSION", "Recorder", "NullRecorder", "Metrics",
    "span", "event", "recording", "get_recorder", "set_recorder",
    "read_events",
    "inc", "observe",
    "log", "info", "debug", "warn", "set_verbosity", "get_verbosity",
    "jaxmon", "report",
    # flight recorder (lazy imports below: timeline/slo/traindiag pull
    # numpy/jnp machinery the bare tracing hooks don't need)
    "Timeline", "SLOConfig", "TrainDiag",
]


def __getattr__(name):
    if name in ("Timeline", "write_timeline", "read_timeline"):
        from repro.obs import timeline
        return getattr(timeline, name)
    if name in ("SLOConfig", "SLOReport"):
        from repro.obs import slo
        return getattr(slo, name)
    if name in ("TrainDiag",):
        from repro.obs import traindiag
        return getattr(traindiag, name)
    raise AttributeError(f"module 'repro.obs' has no attribute {name!r}")
