"""Decide ``correct``: a sample of the candidate evaluations that the
window completed, drawn from the seed, against the plain reference
(`bench/reference/printed_mlp.py`) at the same data seed.

Each sampled evaluation is one answer: the program's netlist-exact test
accuracy and printed area for one genome. The reference repeats the whole
evaluation of that genome on its own (data, pretrain, QAT finetune,
fixed-point compile, integer circuit, price) and the run compares:

* ``acc_gap``: the widest gap between the program's accuracy and the
  reference's, as a share of the test rows;
* ``area_gap``: the widest gap between the two areas, as a share of the
  reference's area;
* ``disagree_share``: the share of the sampled answers whose accuracy or
  area the reference does not reproduce (a gap over ``AGREE``).

A cell's limits file names the numbers it compares, each with its limit;
the others are printed beside them as facts. The sample holds the largest
circuit of the window and ``n - 1`` more drawn from the seed. Quarantined
candidates are left out: they count as failed.
"""
from __future__ import annotations

import time
from typing import Dict, List, Tuple

import numpy as np

from harness.jobs import STREAM_CHECK, Window, derive

# Below this an answer agrees: areas are float sums of the same integer
# terms, and one test row moves an accuracy by 1 / test rows.
AGREE = 1e-9


def sample(window: Window, n: int, seed: int) -> List[Tuple[str, object]]:
    """[(spec_json, program EvalResult)] for the sampled answers."""
    bad = {q.spec_json for q in window.quarantine}
    done = sorted((k, r) for k, r in window.record.items() if k not in bad)
    if not done:
        return []
    largest = max(range(len(done)), key=lambda i: done[i][1].area_mm2)
    rest = [i for i in range(len(done)) if i != largest]
    rng = np.random.default_rng(derive(seed, STREAM_CHECK))
    pick = rng.choice(len(rest), size=min(n - 1, len(rest)), replace=False)
    return [done[largest]] + [done[rest[i]] for i in sorted(pick)]


def per_answer(answers: List[Dict], reference: List[Dict]
               ) -> List[Tuple[float, float]]:
    """(accuracy gap, relative area gap) of each paired evaluation."""
    return [(abs(a["accuracy"] - r["accuracy"]),
             abs(a["area_mm2"] - r["area_mm2"]) / r["area_mm2"])
            for a, r in zip(answers, reference)]


def gaps(answers: List[Dict], reference: List[Dict]) -> Dict[str, float]:
    """Every number the check can compare, over paired evaluations."""
    each = per_answer(answers, reference)
    return {"acc_gap": float(max(a for a, _ in each)),
            "area_gap": float(max(r for _, r in each)),
            "disagree_share": sum(max(a, r) > AGREE for a, r in each)
            / len(each)}


def limited(limits: Dict) -> Dict[str, float]:
    """The numbers a cell compares: its limits file's numeric entries."""
    return {k: v for k, v in limits.items()
            if isinstance(v, (int, float)) and not isinstance(v, bool)}


def judge(found: Dict[str, float], limits: Dict) -> Tuple[bool, Dict]:
    """-> (every compared number within its limit, {name: {"value",
    "limit"}})."""
    numbers = {k: {"value": found[k], "limit": v}
               for k, v in limited(limits).items()}
    if not numbers:
        raise KeyError("the limits name no number to compare")
    return all(n["value"] <= n["limit"] for n in numbers.values()), numbers


def reference_answers(config: Dict, mix: Dict, data_seed: int,
                      specs: List[str], dtype=None) -> List[Dict]:
    import jax.numpy as jnp

    from reference.printed_mlp import Reference
    ref = Reference(config, data_seed, dtype=dtype or jnp.float32)
    lr = config["assumed"]["training"]["finetune_lr"]
    return [ref.evaluate(s, epochs=mix["epochs"], lr=lr) for s in specs]


def run_check(config: Dict, mix: Dict, limits: Dict, window: Window,
              seed: int, data_seed: int) -> Tuple[bool, Dict, Dict]:
    """-> (correct, {name: {"value", "limit"}}, facts for stderr)."""
    t0 = time.perf_counter()
    picked = sample(window, mix["check"]["sample"], seed)
    if not picked:
        return False, {}, {"sampled": 0}
    answers = [{"accuracy": r.accuracy, "area_mm2": r.area_mm2}
               for _, r in picked]
    ref = reference_answers(config, mix, data_seed, [s for s, _ in picked])
    found = gaps(answers, ref)
    correct, numbers = judge(found, limits)
    facts = {k: v for k, v in found.items() if k not in numbers}
    return correct, numbers, dict(facts, sampled=len(picked),
                                  reference_s=time.perf_counter() - t0)


def control_answers(config: Dict, mix: Dict, data_seed: int,
                    specs: List[str]) -> List[Dict]:
    """The control: the reference itself in bfloat16, the precision below
    the configuration's float32, put in the program's place."""
    import jax.numpy as jnp
    return reference_answers(config, mix, data_seed, specs,
                             dtype=jnp.bfloat16)


def describe(numbers: Dict) -> List[str]:
    return [f"check {k} {v['value']!r} limit {v['limit']!r}"
            for k, v in numbers.items()]

