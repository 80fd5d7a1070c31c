"""Evaluator, unnamed remainder: host milliseconds per generation inside
the program's ``eval.population`` spans that none of their direct children
(joined by the records' ``parent``) cover."""

from harness.programspans import self_s


def read(a):
    return a.per_generation_ms(self_s(a.spans, "eval.population"))
