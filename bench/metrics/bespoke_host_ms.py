"""Bespoke fixed-point compile (minimize.compile_bespoke): host milliseconds
per generation inside the program's ``minimize.compile_bespoke`` spans,
one per candidate, with their eager clustering and quantization dispatches."""

from harness.programspans import span_s


def read(a):
    return a.per_generation_ms(span_s(a.spans, "minimize.compile_bespoke"))
