"""Pricing (hw_model.mlp_cost_batch): host milliseconds per generation inside
the program's ``eval.price`` span -- stacking the integer weights and
codebooks, the vectorized pricing call and the result list."""

from harness.programspans import span_s


def read(a):
    return a.per_generation_ms(span_s(a.spans, "eval.price"))
