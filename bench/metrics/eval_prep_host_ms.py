"""Evaluator set-up (batch_eval.evaluate_population): host milliseconds per
generation inside the program's ``eval.prepare`` span -- dedupe, cache
lookup, the pretrain memo, stacking genomes and masks, and the uploads."""

from harness.programspans import span_s


def read(a):
    return a.per_generation_ms(span_s(a.spans, "eval.prepare"))
