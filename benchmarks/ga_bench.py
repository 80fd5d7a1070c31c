"""GA hot-path benchmark: population evaluation, cold and warm.

Measures the wall-clock of evaluating one NSGA-II generation (population of
fresh specs) on the paper's UCI MLPs through
`batch_eval.evaluate_population` (one vmap-over-scan jit for the whole
population + one packed netlist launch + one vectorized pricing pass):

* cold  — the first generation, which pays the population's compiles;
* warm  — a second generation of fresh specs, the steady-state GA cost,
          where the population jit is already compiled.

The warm generation is then re-run under a live `repro.obs` tracer: the
bench emits the trace JSONL (eval.batch / eval.finetune /
eval.compile_price spans for the whole stack) and reports the relative
tracing overhead against the untraced warm lap.
"""
from __future__ import annotations

import random
import tempfile
import time
from pathlib import Path
from typing import Dict, List

from repro.obs import trace as TR

from repro.configs.printed_mlp import PRINTED_MLPS
from repro.core import batch_eval as BE
from repro.core import ga as GA
from repro.core import minimize as MZ
from repro.core.compression_spec import ModelMin


def _random_population(n_layers: int, population: int,
                       seed: int) -> List[ModelMin]:
    rng = random.Random(seed)
    cfg = GA.GAConfig()
    return [ModelMin(tuple(GA._random_gene(rng, cfg)
                           for _ in range(n_layers)))
            for _ in range(population)]


def run(dataset: str = "whitewine", *, population: int = 16,
        epochs: int = 90, seed: int = 0) -> Dict:
    cfg = PRINTED_MLPS[dataset]
    n_layers = len(cfg.layer_dims) - 1
    MZ.pretrain(cfg, seed=seed)          # outside the timed laps

    gen0 = _random_population(n_layers, population, seed)
    gen1 = _random_population(n_layers, population, seed + 1)

    t0 = time.time()
    BE.evaluate_population(cfg, gen0, epochs=epochs, seed=seed)
    t_cold = time.time() - t0

    t0 = time.time()
    BE.evaluate_population(cfg, gen1, epochs=epochs, seed=seed)
    t_warm = time.time() - t0

    # same warm generation under a live tracer: emit the trace file and
    # price the telemetry against the untraced warm lap
    trace_path = Path(tempfile.mkdtemp(prefix="repro_obs_bench_")) \
        / "ga_bench_trace.jsonl"
    with TR.capture(trace_path):
        t0 = time.time()
        BE.evaluate_population(cfg, gen1, epochs=epochs, seed=seed)
        t_traced = time.time() - t0
    records, damaged = TR.read_trace(trace_path)
    assert damaged == 0 and records, "bench trace unreadable"
    trace_overhead = max(0.0, t_traced / t_warm - 1.0)

    return {
        "dataset": dataset, "population": population, "epochs": epochs,
        "t_cold_s": t_cold, "t_warm_s": t_warm,
        "t_traced_s": t_traced,
        "trace_overhead_pct": trace_overhead * 100.0,
        "trace_path": str(trace_path),
        "trace_records": len(records),
    }


def main(fast: bool = False):
    kw = dict(population=8, epochs=40) if fast else {}
    res = run(**kw)
    print("ga_bench (one NSGA-II generation through the population "
          "engine)")
    print(f"dataset={res['dataset']} population={res['population']} "
          f"epochs={res['epochs']}")
    print(f"  cold  {res['t_cold_s']:7.1f} s/generation (compiles included)")
    print(f"  warm  {res['t_warm_s']:7.1f} s/generation  <- steady-state GA "
          f"cost")
    print(f"  tracing overhead {res['trace_overhead_pct']:6.2f} % on the "
          f"warm lap ({res['trace_records']} records -> "
          f"{res['trace_path']})")
    return res


if __name__ == "__main__":
    main()
