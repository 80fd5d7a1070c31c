"""Approximation bench: budgeted pass sweeps + the approximation-aware GA.

Part A — **budget sweep**: for each printed-MLP dataset, the minimized
design point (4-bit / 0.4-sparsity / 8-cluster) is lowered to its bespoke
netlist and greedily approximated (`approx.fit_budget`) under three
worst-case logit-error budgets (fractions of the logit range). Per row:
the knobs chosen, the analyzer's PROVEN error bound vs the measured max
logit error on the full test set (soundness — asserted: measured <=
bound), area before/after, and netlist-exact accuracy before/after.

Part B — **GA with approximation genes**: the combined hardware-aware
search on one dataset, once with the paper's exact genome and once with
the circuit-approximation genes enabled. Acceptance (asserted): the
approximating run reaches a Pareto point with LOWER area than the best
exact point at <= 5% accuracy drop from the dense 8-bit baseline — the
next multiplier beyond minimization (Armeniakos DATE'22).
"""
from __future__ import annotations

import time
from typing import Dict, List, Optional

import numpy as np

from repro import approx, circuit
from repro.configs.printed_mlp import PRINTED_MLPS
from repro.core import batch_eval as BE
from repro.core import minimize as MZ
from repro.core.compression_spec import ModelMin

BUDGET_FRACS = (0.002, 0.01, 0.05)


def budget_sweep(datasets: Optional[List[str]] = None, *,
                 epochs: int = 60, seed: int = 0) -> List[Dict]:
    rows = []
    for name in (datasets or sorted(PRINTED_MLPS)):
        cfg = PRINTED_MLPS[name]
        n_layers = len(cfg.layer_dims) - 1
        spec = ModelMin.uniform(n_layers, bits=4, sparsity=0.4, clusters=8,
                                input_bits=cfg.input_bits)
        # the full minimization recipe (QAT finetune under the spec), the
        # evaluator's at P = 1 — the rows really are the minimized design
        # point
        _, (_, _, xte, yte) = MZ.pretrain(cfg, seed=seed)
        net, compiled = circuit.compile_spec(cfg, spec, epochs=epochs,
                                             seed=seed)
        sc = circuit.structural_cost(net)
        acc0 = circuit.netlist_accuracy(net, compiled, xte, yte)
        for frac in BUDGET_FRACS:
            budget = approx.logit_budget(net, frac)
            t0 = time.perf_counter()
            params, anet, rep = approx.fit_budget(net, budget)
            fit_ms = (time.perf_counter() - t0) * 1e3
            measured = approx.measured_max_logit_error(anet, compiled, xte)
            acc = circuit.netlist_accuracy(anet, compiled, xte, yte)
            asc = circuit.structural_cost(anet)
            rows.append({
                "dataset": name, "budget_frac": frac, "budget": budget,
                "bound": rep.bound, "measured": measured,
                "sound": measured <= rep.logit_bound,
                "exact_area_mm2": sc.area_mm2,
                "approx_area_mm2": asc.area_mm2,
                "area_gain": sc.area_mm2 / max(asc.area_mm2, 1e-9),
                "exact_acc": acc0, "approx_acc": acc,
                "csd_drop": params.csd_drop, "lsb": params.lsb,
                "argmax_lsb": params.argmax_lsb, "fit_ms": fit_ms,
            })
    return rows


def ga_compare(dataset: str = "seeds", *, population: int = 10,
               generations: int = 4, epochs: int = 40,
               seed: int = 0) -> Dict:
    """Exact-genome GA vs approximation-genome GA on one dataset. Both use
    the netlist-exact accuracy objective so the comparison is apples to
    apples on the simulated printed datapath."""
    import sys
    from pathlib import Path
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
    from benchmarks import fig2_combined

    cfg = PRINTED_MLPS[dataset]
    base = MZ.baseline(cfg)
    floor = base.accuracy - 0.05

    out = {}
    for tag, ax in (("exact", False), ("approx", True)):
        res = fig2_combined.run(dataset, population=population,
                                generations=generations, epochs=epochs,
                                seed=seed, netlist=True, approx=ax)
        # best (min-area) evaluated point within the 5%-loss envelope,
        # split by whether the candidate carries approximation genes
        best: Dict[str, Optional[float]] = {"exact": None, "approx": None}
        for spec_json, objs in res["evaluations"].items():
            acc, area = 1.0 - objs[0], objs[1]
            kind = ("approx" if ModelMin.from_json(spec_json).has_approx
                    else "exact")
            if acc >= floor and (best[kind] is None or area < best[kind]):
                best[kind] = area
        out[tag] = {"front": res["pareto_front"],
                    "n_evaluations": res["n_evaluations"],
                    "best_exact_area": best["exact"],
                    "best_approx_area": best["approx"]}

    exact_best = out["exact"]["best_exact_area"]
    # the approximating run sees exact candidates too — its exact best can
    # only improve on the exact run's; compare its approx best to the
    # tightest exact area either run found
    cands = [out[t]["best_exact_area"] for t in out
             if out[t]["best_exact_area"] is not None]
    tightest_exact = min(cands) if cands else exact_best
    return {
        "dataset": dataset, "baseline_acc": base.accuracy, "floor": floor,
        "best_exact_area": tightest_exact,
        "best_approx_area": out["approx"]["best_approx_area"],
        "runs": out,
    }


def main(fast: bool = False):
    t0 = time.time()
    rows = budget_sweep(["seeds", "whitewine"] if fast else None)
    print("approx_bench A: greedy budgeted approximation "
          "(proven worst-case logit-error bounds)")
    print("dataset,budget_frac,bound,measured,sound,area_exact,area_approx,"
          "gain,acc_exact,acc_approx,knobs")
    ok = True
    for r in rows:
        knobs = (f"csd{list(r['csd_drop'])}/lsb{list(r['lsb'])}"
                 f"/am{r['argmax_lsb']}")
        print(f"{r['dataset']},{r['budget_frac']},{r['bound']},"
              f"{r['measured']},{r['sound']},{r['exact_area_mm2']:.0f},"
              f"{r['approx_area_mm2']:.0f},{r['area_gain']:.2f},"
              f"{r['exact_acc']:.3f},{r['approx_acc']:.3f},{knobs}")
        ok &= r["sound"]
    assert ok, "measured logit error exceeded the analyzer's bound"

    ga = ga_compare(population=8 if fast else 10,
                    generations=3 if fast else 4,
                    epochs=30 if fast else 40)
    print(f"\napprox_bench B: GA with approximation genes "
          f"({ga['dataset']}, acc floor {ga['floor']:.3f})")
    be, ba = ga["best_exact_area"], ga["best_approx_area"]
    print(f"best exact-point area   : "
          f"{'-' if be is None else f'{be:.1f} mm2'}")
    print(f"best approx-point area  : "
          f"{'-' if ba is None else f'{ba:.1f} mm2'}")
    wins = ba is not None and be is not None and ba < be
    print(f"acceptance (approx Pareto point beats best exact at <=5% "
          f"loss): {'PASS' if wins else 'FAIL'}")
    assert wins, "approximation genes failed to beat the exact frontier"
    print(f"[{time.time()-t0:.0f}s]")
    return {"budget_sweep": rows, "ga": ga}


if __name__ == "__main__":
    main()
