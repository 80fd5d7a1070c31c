"""Netlist lowering (circuit.compile_netlist): host milliseconds per
generation inside the program's ``circuit.compile`` spans, one per candidate."""

from harness.programspans import span_s


def read(a):
    return a.per_generation_ms(span_s(a.spans, "circuit.compile"))
