"""Split serving: the chip's idle time per traced request after the
program's ``repro.split.infer`` returns, until the caller holds the
logits (``bench.infer``'s end): the result handoff and the fetch."""
import program_trace


def read(run):
    return program_trace.idle_ms(run, "fetch")
