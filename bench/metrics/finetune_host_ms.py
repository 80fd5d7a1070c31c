"""QAT finetune, host side: milliseconds per generation inside the
program's ``eval.finetune`` span (dispatch to ready) and its ``eval.fetch``
span (the trained population's device-to-host copy)."""

from harness.programspans import span_s


def read(a):
    return a.per_generation_ms(span_s(a.spans, "eval.finetune", "eval.fetch"))
