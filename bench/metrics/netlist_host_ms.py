"""Netlist simulation, host side: milliseconds per generation inside the
program's ``kernels.netlist_sim`` span -- wave schedule, uploads, the
per-tile launches and their syncs."""

from harness.programspans import span_s


def read(a):
    return a.per_generation_ms(span_s(a.spans, "kernels.netlist_sim"))
