"""Split serving: the chip's idle time per traced request while the
program dispatches it (inside ``repro.split.infer``: head, link, tail)."""
import program_trace


def read(run):
    return program_trace.idle_ms(run, "dispatch")
