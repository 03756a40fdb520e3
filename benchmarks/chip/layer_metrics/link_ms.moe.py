"""Mellum split serving: mean host time of the link
(``repro.split.link``: the cut activation's int8 quantize and
dequantize) over the traced w8 requests. Host clock only, so a CPU trace
reads it too."""
import program_trace


def read(run):
    return program_trace.span_ms(run, "split.link", "w8")
