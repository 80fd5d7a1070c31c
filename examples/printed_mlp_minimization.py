"""End-to-end printed-MLP minimization demo (the paper, on one dataset).

Walks the full pipeline on the WhiteWine classifier with the batched
population engine:

  1. FP32 pretrain the baseline bespoke MLP (MICRO'20 un-minimized design)
     and price it in printed EGT area/power;
  2. Fig. 1 slice — evaluate a quantization sweep as ONE batched population
     call (every bit width QAT-finetuned in a single vmapped jit);
  3. Fig. 2 — the hardware-aware NSGA-II over bits x sparsity x clusters,
     every generation evaluated through `core.batch_eval`, with the
     persistent on-disk cache so a re-run costs nothing;
  4. report the Pareto front and the area gain at <=5% accuracy loss
     (paper: up to ~8x for the combined search).

Run:  PYTHONPATH=src python examples/printed_mlp_minimization.py
      (add --full for the paper-sized budget)
"""
from __future__ import annotations

import argparse
import sys
import time

from repro.configs import backend
from repro.configs.printed_mlp import PRINTED_MLPS
from repro.core import batch_eval as BE
from repro.core import minimize as MZ
from repro.core.compression_spec import ModelMin
from repro.core.pareto import gain_at_loss, pareto_front


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="whitewine",
                    choices=sorted(PRINTED_MLPS))
    ap.add_argument("--full", action="store_true",
                    help="paper-sized budget (slower)")
    ap.add_argument("--cache-dir", default=".eval_cache",
                    help="persistent evaluation cache dir (stable default "
                         "so a re-run retrains nothing)")
    args = ap.parse_args(argv)
    backend.enable_compile_cache()

    cfg = PRINTED_MLPS[args.dataset]
    n_layers = len(cfg.layer_dims) - 1
    epochs = 90 if args.full else 60
    cache_dir = args.cache_dir
    cache = BE.EvalCache(f"{cache_dir}/{cfg.name}_evals.json")

    # -- 1. baseline ------------------------------------------------------
    t0 = time.time()
    base = MZ.baseline(cfg)
    print(f"[{cfg.name}] baseline (dense 8-bit bespoke): "
          f"acc={base.accuracy:.3f} area={base.area_mm2/100:.1f} cm2 "
          f"power={base.power_mw:.1f} mW "
          f"({base.n_multipliers} multipliers)  [{time.time()-t0:.0f}s]")

    # -- 2. Fig. 1 slice: quantization sweep as one batched call ----------
    t0 = time.time()
    sweep = [ModelMin.uniform(n_layers, bits=b, input_bits=cfg.input_bits)
             for b in range(2, 8)]
    results = BE.evaluate_population(cfg, sweep, epochs=epochs, cache=cache)
    print(f"quantization sweep (one batched call, {len(sweep)} specs, "
          f"{time.time()-t0:.0f}s):")
    for r in results:
        gain = base.area_mm2 / max(r.area_mm2, 1e-9)
        print(f"  {r.spec.layers[0].bits}-bit: acc={r.accuracy:.3f} "
              f"area={r.area_mm2/100:6.2f} cm2 ({gain:.1f}x)")

    # -- 3. Fig. 2: hardware-aware GA through the batched engine ----------
    from pathlib import Path
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
    from benchmarks import fig2_combined
    t0 = time.time()
    res = fig2_combined.run(
        args.dataset, cache_dir=cache_dir, epochs=epochs,
        **({} if args.full else dict(population=8, generations=3)))
    print(f"GA search: {res['n_evaluations']} unique evaluations in "
          f"{time.time()-t0:.0f}s (cache: {cache_dir})")

    # -- 4. report --------------------------------------------------------
    print(f"combined gain at <=5% accuracy loss: "
          f"{res['combined_gain_at_5pct']:.2f}x (paper: up to ~8x)")
    print("pareto front (acc, area cm2, critical path, spec):")
    for acc, area, delay, spec in res["pareto_front"][:8]:
        print(f"  acc={acc:.3f} area={area/100:7.2f} cm2 "
              f"delay={delay:3d} stages  {spec}")

    # -- 5. compile the chosen point to an actual bespoke circuit ---------
    # pick the cheapest front member within 5% accuracy loss of the
    # baseline (the paper's max-gain operating point) and materialize it:
    # netlist, bit-exact simulated accuracy, structural-vs-analytic
    # pricing, delay
    from repro import circuit
    eligible = [(acc, area, spec) for acc, area, _, spec
                in res["pareto_front"] if acc >= base.accuracy - 0.05]
    if eligible:
        chosen = min(eligible, key=lambda t: t[1])[2]   # cheapest eligible
    else:
        chosen = max(res["pareto_front"], key=lambda t: t[0])[3]
    spec = ModelMin.from_json(chosen)
    net, compiled = circuit.compile_spec(cfg, spec, epochs=epochs)
    _, _, xte, yte = MZ.dataset_for(cfg)
    sc = circuit.structural_cost(net)
    cv = circuit.cross_validate(net, compiled)
    acc_exact = circuit.netlist_accuracy(net, compiled, xte, yte)
    print(f"\ncompiled circuit for the chosen point {chosen}:")
    print(circuit.describe(net, sc))
    print(f"netlist-exact accuracy: {acc_exact:.3f} "
          f"(float emulation: {MZ.compiled_accuracy(compiled, xte, yte):.3f})")
    print(f"structural cost == analytic hw_model: {cv['ok']}")

    # -- 6. approximate the circuit itself under an error budget ----------
    # beyond minimization: the approx pass pipeline (truncated-CSD
    # coefficients, accumulator LSB truncation, comparator narrowing)
    # greedily trades PROVEN worst-case logit error for area
    from repro import approx
    budget = approx.logit_budget(net, 0.01)       # 1% of the logit range
    _, anet, rep = approx.fit_budget(net, budget)
    acc_approx = circuit.netlist_accuracy(anet, compiled, xte, yte)
    asc = circuit.structural_cost(anet)
    print(f"\napproximated under a {budget}-LSB logit-error budget "
          f"(proven bound: {rep.bound}):")
    print(f"  knobs: {rep.params}")
    print(f"  area {sc.area_mm2/100:.2f} -> {asc.area_mm2/100:.2f} cm2 "
          f"({rep.area_gain:.2f}x on top of minimization), "
          f"accuracy {acc_exact:.3f} -> {acc_approx:.3f}")
    res.update(chosen=chosen, chosen_netlist_accuracy=acc_exact)
    return res


if __name__ == "__main__":
    main()
