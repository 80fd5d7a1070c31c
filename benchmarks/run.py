"""Benchmark harness: one function per paper table/figure + framework
benches. Prints ``name,us_per_call,derived`` CSV summaries at the end.

  fig1_standalone — paper Fig. 1 (standalone technique Pareto fronts)
  fig2_combined   — paper Fig. 2 (hardware-aware GA, combined techniques)
  area_table      — paper §III baseline circuit table
  kernel_bench    — per-kernel derived TPU roofline
  roofline_table  — §Roofline across all dry-run cells
  ga_bench        — GA hot path: serial vs batched population evaluation
  circuit_bench   — bespoke netlist compile / bit-exact sim / delay
  netlist_bench   — netlist-exact vs analytic GA generation (<=2x gate)
  approx_bench    — budgeted circuit approximation + approximation-GA
  search_bench    — island runtime: throughput / checkpoint / resume cost

``python -m benchmarks.run [--fast] [--only NAME] [--compare BASELINE]``

``--compare`` reads a previously-saved ``name,us_per_call,...`` CSV (e.g.
the committed ``benchmarks/baseline.csv``) and warns on every bench whose
wall-clock regressed more than 15% against it — names missing on either
side are skipped, so partial runs (``--only``) compare cleanly.

Every bench row also carries executable-observatory profile columns
(`repro.obs.xprof` / `repro.obs.metrics`, no tracing required):

  compiles      — XLA backend compiles during the bench (count_compiles)
  compile_s     — wall-clock spent inside the backend compiler
  pad_waste_pct — % of packed slots (netlist lanes + eval bucket specs)
                  burned on NOP/replica padding, from the always-on
                  padding counters' before/after deltas

``--compare`` warns on these too: a bench whose compile count grew >25%
(and by at least 5 compiles) over baseline is flagged as a recompile
regression even when the wall-clock still squeaks under the 15% gate —
compile churn hides inside timing noise long before it dominates it.
"""
from __future__ import annotations

import argparse
import time
from pathlib import Path
from typing import Dict

from repro.configs import backend
from repro.obs import metrics as MT
from repro.obs import xprof

from benchmarks import approx_bench, area_table, circuit_bench, \
    dryrun_memory_table, fig1_standalone, fig2_combined, ga_bench, \
    kernel_bench, netlist_bench, roofline_table, search_bench

BENCHES = [
    ("area_table", area_table.main),
    ("fig1_standalone", fig1_standalone.main),
    ("fig2_combined", fig2_combined.main),
    ("kernel_bench", kernel_bench.main),
    ("roofline_table", roofline_table.main),
    ("dryrun_memory_table", dryrun_memory_table.main),
    ("ga_bench", ga_bench.main),
    ("circuit_bench", circuit_bench.main),
    ("netlist_bench", netlist_bench.main),
    ("approx_bench", approx_bench.main),
    ("search_bench", search_bench.main),
]


_PAD_COUNTERS = (  # (real, total) always-on padding accounts, in "slots"
    ("netlist_sim.pad.lanes_used", "netlist_sim.pad.lanes_total"),
    ("eval.pad.specs_real", "eval.pad.specs_total"),
)


def _pad_totals() -> Dict[str, int]:
    return {n: MT.counter(n).value for pair in _PAD_COUNTERS for n in pair}


def _pad_waste_pct(before: Dict[str, int], after: Dict[str, int]) -> float:
    """% of packed slots that were padding during the window, over the
    netlist lane and eval bucket accounts combined. 0 when nothing packed."""
    real = total = 0
    for r, t in _PAD_COUNTERS:
        real += after[r] - before[r]
        total += after[t] - before[t]
    return 100.0 * (1.0 - real / total) if total > 0 else 0.0


def load_baseline(path) -> Dict[str, Dict[str, float]]:
    """``name,us_per_call[,compiles,...]`` CSV -> {name: {us, compiles}}.
    Header lines and unparsable rows are skipped; profile columns are
    optional so pre-observatory baselines still compare on wall-clock."""
    out: Dict[str, Dict[str, float]] = {}
    for line in Path(path).read_text().splitlines():
        parts = line.strip().split(",")
        if len(parts) < 2 or parts[0] == "name":
            continue
        try:
            row = {"us": float(parts[1])}
        except ValueError:
            continue
        if len(parts) >= 3:
            try:
                row["compiles"] = float(parts[2])
            except ValueError:
                pass
        out[parts[0]] = row
    return out


def compare_against(baseline: Dict[str, Dict[str, float]],
                    current: Dict[str, Dict[str, float]],
                    threshold: float = 0.15,
                    compile_threshold: float = 0.25,
                    compile_floor: int = 5) -> Dict[str, str]:
    """{name: warning text} for regressed benches: wall-clock slower than
    baseline by > ``threshold``, or backend-compile count grown by more
    than ``compile_threshold`` AND at least ``compile_floor`` compiles."""
    out: Dict[str, str] = {}
    for name, cur in current.items():
        base = baseline.get(name)
        if base is None:
            continue
        if base["us"] > 0 and cur["us"] > base["us"] * (1.0 + threshold):
            out[name] = (f"regressed {cur['us'] / base['us'] * 100 - 100:.0f}%"
                         f" wall-clock (>{threshold * 100:.0f}% threshold)")
        elif ("compiles" in base and cur["compiles"] >
                max(base["compiles"] * (1.0 + compile_threshold),
                    base["compiles"] + compile_floor)):
            out[name] = (f"compiled {cur['compiles']:.0f} executables vs "
                         f"{base['compiles']:.0f} at baseline (recompile "
                         "regression: a static-shape key is churning)")
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--only", default=None)
    ap.add_argument("--compare", default=None, metavar="BASELINE_CSV",
                    help="warn on benches >15%% slower than this "
                         "name,us_per_call CSV")
    args = ap.parse_args()
    backend.enable_compile_cache()

    csv = []
    current: Dict[str, Dict[str, float]] = {}
    for name, fn in BENCHES:
        if args.only and name != args.only:
            continue
        print(f"\n=== {name} {'=' * (60 - len(name))}")
        pad0 = _pad_totals()
        t0 = time.time()
        with xprof.count_compiles() as cc:
            fn(fast=args.fast)
        us = (time.time() - t0) * 1e6
        waste = _pad_waste_pct(pad0, _pad_totals())
        current[name] = {"us": us, "compiles": float(cc.compiles)}
        csv.append(f"{name},{us:.0f},{cc.compiles},{cc.compile_s:.2f},"
                   f"{waste:.1f},see-above")
    print("\nname,us_per_call,compiles,compile_s,pad_waste_pct,derived")
    for line in csv:
        print(line)

    if args.compare:
        regressions = compare_against(load_baseline(args.compare), current)
        for name, why in sorted(regressions.items()):
            print(f"WARNING: {name} {why} vs {args.compare}")
        if not regressions:
            print(f"compare: no regressions vs {args.compare}")


if __name__ == "__main__":
    main()
