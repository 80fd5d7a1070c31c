"""Input quantization and packing: host milliseconds per generation inside
the program's ``netlist_sim.pack`` span -- node tables per candidate,
ADC-quantized inputs and the stacked population."""

from harness.programspans import span_s


def read(a):
    return a.per_generation_ms(span_s(a.spans, "netlist_sim.pack"))
