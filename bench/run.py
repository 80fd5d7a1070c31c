"""One run of one benchmark cell on the chip.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Set-up makes the configuration's dataset and its pretrain, warms every
population size a generation can ask for and every netlist launch bucket
the search can reach, and runs a warm-up search job on a GA seed of its
own; then the window runs whole NSGA-II search jobs, each
with a GA seed drawn from --seed, back to back for --seconds and closes at
the next generation boundary. A traced run traces a window of at most
TRACE_SECONDS.
After the window a sample of its evaluations is compared with the plain
reference. The last line of standard output is one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics`` and ``device`` (and
``breakdown`` when traced); the numbers compared and their limits end
standard error. Exit codes: 0 a result was printed, 1 an error, 2 no
program in the checkout, 3 no TPU or too few chips.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

from harness import cell as CELL  # noqa: E402
from harness import jobs as J  # noqa: E402

ARTIFACTS = CELL.ROOT / "artifacts" / "bench"
COMPILE_CACHE = CELL.ROOT / ".jax_cache"
# The profiler keeps a bounded number of device events, and the finetune's
# scan issues tens of thousands per launch: past about 20 s of window the
# trace loses device time (finetune time per generation read 25 ms in 51 s
# traces against 35 ms in 10 s and 20 s ones).
TRACE_SECONDS = 15.0


def _args(argv):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def configure_compile_cache(jax) -> None:
    """Persist every compiled program, however quick to compile, in the
    checkout's fixed cache directory (or where JAX_COMPILATION_CACHE_DIR
    says), so that only a cell's first run in a checkout compiles."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def setup(search, cell, seed: int, test_rows: int) -> None:
    """Warm the cell's own shapes: one evaluation of every population
    size a generation can ask for (the finetune's power-of-two buckets and
    the slices back to the real candidates), one launch of each netlist
    engine bucket the search can reach, then warm-up search jobs."""
    from harness.warm import netlist_buckets
    mix = cell.mix
    warm = J.Window(0.0)
    base = J.derive(seed, J.STREAM_WARMUP)
    for n in range(1, mix["population"] + 1):
        search.evaluate(search.random_specs(n, (base + n) % (1 << 31)), warm)
    netlist_buckets(search.P, cell.config, mix, test_rows)
    search.run(warm, lambda j: (base + 1000 + j) % (1 << 31), start=False,
               max_jobs=mix["warmup"]["jobs"])


def end_to_end(window, setup_s: float):
    return {"evals_per_s": window.evaluations / window.elapsed,
            "gen_s": window.elapsed / window.generations,
            "setup_s": setup_s}


def main(argv=None) -> int:
    args = _args(argv)
    try:
        cell = CELL.load(args.workload)
    except (KeyError, FileNotFoundError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    if not (CELL.ROOT / "src" / "repro").is_dir():
        print(f"bench: no program under {CELL.ROOT / 'src'}",
              file=sys.stderr)
        return 2

    import jax

    from harness import device as DEV
    from harness.monitor import CompileMonitor
    configure_compile_cache(jax)
    monitor = CompileMonitor()
    try:
        devices = jax.devices()
        dev = DEV.require_tpu(devices, cell.chips)
    except (DEV.NoChip, RuntimeError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    peaks = DEV.peaks(dev["kind"])

    from harness.program import Program
    program = Program()
    cfg = program.config(cell.config)
    data_seed = cell.config["assumed"]["dataset"]["seed"]
    search = J.Search(program, cfg, cell.mix, data_seed)

    art = ARTIFACTS / args.workload
    if args.trace:
        shutil.rmtree(art, ignore_errors=True)
        art.mkdir(parents=True)
        program.TR.start(art / "spans.jsonl")

    rows = data_rows(cell.config)
    setup(search, cell, args.seed, rows[1])

    window = J.Window(min(args.seconds, TRACE_SECONDS) if args.trace
                      else args.seconds)
    job_seed = lambda j: J.derive(args.seed, J.STREAM_JOB0 + j)  # noqa: E731
    if args.trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(art / "xprof"), profiler_options=opts)
        note = jax.profiler.TraceAnnotation("bench.window")
        note.__enter__()
        program.TR.event("bench.window", phase="start")
    c0 = monitor.snapshot()
    search.run(window, job_seed, trace=bool(args.trace))
    c1 = monitor.snapshot()
    setup_s = window.t0 - T_START
    if args.trace:
        program.TR.event("bench.window", phase="end")
        note.__exit__(None, None, None)
        jax.profiler.stop_trace()
        program.TR.stop()
    mem = DEV.memory_peak_bytes(devices[:cell.chips])
    compiles = c1[0] - c0[0]

    out = {"device": dict(dev, memory_peak_bytes=mem)}
    if args.trace:
        metrics, breakdown, dev_extra = traced_metrics(
            cell, window, art, peaks, compiles, rows[0])
        out["device"].update(dev_extra)
    else:
        vals = end_to_end(window, setup_s)
        metrics = {m["name"]: {"value": vals[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
        breakdown = None

    from harness.check import describe, run_check
    correct, numbers, facts = run_check(cell.config, cell.mix, cell.limits,
                                        window, args.seed, data_seed)
    print(f"bench: {args.workload} seed {args.seed}: {window.generations} "
          f"generations, {window.evaluations} evaluations, "
          f"{window.jobs_started} jobs in {window.elapsed!r} s; setup "
          f"{setup_s!r} s; window programs built {compiles} "
          f"({c1[1] - c0[1]} loaded from the compile cache, "
          f"{c1[2] - c0[2]!r} s); quarantined {len(window.quarantine)}; "
          f"reference {facts}", file=sys.stderr)
    for line in describe(numbers):
        print(line, file=sys.stderr)
    result = {"correct": bool(correct),
              "attempted": window.evaluations,
              "failed": len(window.quarantine),
              "metrics": metrics}
    result.update(out)
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["check"] = numbers
    print(json.dumps(result), flush=True)
    return 0


def traced_metrics(cell, window, art, peaks, compiles, train_rows):
    from harness import spans as SP
    from harness import xtrace as XT
    from harness.readers import Artifacts, read
    trace = XT.load(XT.find_xplane(art / "xprof"))
    lo, hi = trace.window()
    a = Artifacts(window=window, trace=trace, lo=lo, hi=hi,
                  spans=SP.in_window(art / "spans.jsonl"),
                  config=cell.config, mix=cell.mix, peaks=peaks,
                  train_rows=train_rows,
                  compiles=compiles)
    metrics = {}
    for m in cell.per_layer:
        v = read(m["name"], a)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    breakdown = {"device_ops": XT.top_device_ops(trace, lo, hi),
                 "idle_gaps": XT.idle_gaps(trace, lo, hi)}
    return metrics, breakdown, {"busy_s": XT.busy_s(trace, lo, hi),
                                "window_s": a.traced_s}


def data_rows(config):
    """-> (train rows, test rows) of the configuration's dataset."""
    from reference.printed_mlp import make_dataset
    data = config["assumed"]["dataset"]
    xtr, _, xte, _ = make_dataset(data, data["seed"])
    return len(xtr), len(xte)


if __name__ == "__main__":
    sys.exit(main())
