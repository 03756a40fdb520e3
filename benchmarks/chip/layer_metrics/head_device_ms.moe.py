"""Mellum split serving: device time of the head program
(``jit_split_head``) per traced request, from the device trace's "XLA
Modules" line."""
import program_trace


def read(run):
    return program_trace.module_ms(run, "jit_split_head")
