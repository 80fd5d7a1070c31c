"""Faults planted under the timed path, to show that the check catches
them: `bench/calibrate.py` reads them on the chip, the CPU tests see each
read ``correct: false``.

* ``state_unchanged``: the finetune returns its state unchanged (the
  pretrained weights for every candidate);
* ``half_batch``: the finetune trains on the first half of the rows and
  takes its mean over them alone;
* ``answer_altered``: the netlist engine's decisions for the last quarter
  of the test rows move to the next class;
* ``csd``, ``lsb``, ``argmax``: one part of the approximation left out of
  ``approx.budget.build_passes``: truncated CSD (``RoundCoeffsCSD``), the
  accumulator truncation (``TruncateAccum``), or the argmax truncation of
  ``SimplifyActs``.

Each is planted by replacing one attribute of the program's modules for
the length of a ``with planted(name):`` block. The program must have been
imported first (`harness.program.Program`).
"""
from __future__ import annotations

import contextlib
from typing import Callable, Dict, Tuple

import numpy as np


def _unchanged(params0, bits, ks, masks, x, y, **kw):
    import jax
    import jax.numpy as jnp
    return jax.tree_util.tree_map(
        lambda a: jnp.broadcast_to(a, (bits.shape[0],) + a.shape), params0)


def _half_batch(orig):
    def finetune(params0, bits, ks, masks, x, y, **kw):
        n = x.shape[0] // 2
        return orig(params0, bits, ks, masks, x[:n], y[:n], **kw)
    return finetune


def _altered_answers(simulate):
    def population_accuracy(pop, x, y, **kw):
        cls = simulate(pop, x, **kw)["argmax"].copy()
        q = cls.shape[1] // 4
        cls[:, -q:] = (cls[:, -q:] + 1) % pop.n_classes
        return np.mean(cls == np.asarray(y)[None, :], axis=1)
    return population_accuracy


def _without(build_passes, left_out: str):
    from repro.approx.passes import RoundCoeffsCSD, SimplifyActs, TruncateAccum
    gone = {"csd": RoundCoeffsCSD, "lsb": TruncateAccum}.get(left_out)

    def build(p):
        out = []
        for x in build_passes(p):
            if gone is not None and isinstance(x, gone):
                continue
            if left_out == "argmax" and isinstance(x, SimplifyActs):
                x = SimplifyActs(0)
            out.append(x)
        return out
    return build


def _target(name: str) -> Tuple[object, str, Callable]:
    """-> (module, attribute, the replacement given the original)."""
    from repro.approx import budget
    from repro.core import batch_eval as BE
    from repro.kernels import netlist_sim as NS
    if name == "state_unchanged":
        return BE, "_population_finetune", lambda orig: _unchanged
    if name == "half_batch":
        return BE, "_population_finetune", _half_batch
    if name == "answer_altered":
        return NS, "population_accuracy", \
            lambda orig: _altered_answers(NS.simulate_population)
    if name in ("csd", "lsb", "argmax"):
        return budget, "build_passes", lambda orig: _without(orig, name)
    raise KeyError(f"no fault {name!r}; known: {sorted(FAULTS)}")


FAULTS: Dict[str, str] = {
    "state_unchanged": "finetune returns its state unchanged",
    "half_batch": "finetune on half the rows",
    "answer_altered": "a quarter of the decisions altered",
    "csd": "RoundCoeffsCSD left out",
    "lsb": "TruncateAccum left out",
    "argmax": "argmax truncation left out",
}


@contextlib.contextmanager
def planted(name: str):
    module, attr, make = _target(name)
    orig = getattr(module, attr)
    setattr(module, attr, make(orig))
    try:
        yield
    finally:
        setattr(module, attr, orig)
