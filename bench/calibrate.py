"""Readings that the correctness limits of a cell are set from.

    python3 bench/calibrate.py --workload <name> --seeds 11,12,... \
        [--control-seeds 21,22,23] [--faults csd,lsb --fault-seeds 31,32,33] \
        [--seconds 20 | --generations 100] [--sample 12]

For each seed, in one process: a short window of the cell's own search
jobs at the cell's sizes, on the configuration's dataset, and the same
sample of its evaluations that a run of ``bench/run.py`` would compare,
against the plain reference. That gives the program's readings of each
number the check can compare (`harness/check.py`). For each control seed
the sample is compared once more: the control (the reference in bfloat16,
put in the program's place) against the reference. For each fault of
`harness/faults.py` and each fault seed, the window runs with the fault
planted under the timed path. One JSON line per reading; the last line
holds, per number, the largest program reading, the smallest control
reading and the smallest reading of each fault. With ``--generations``
the window closes after that many generations, so the work does not
depend on the machine's speed. Runs on the chip, where the program's
arithmetic is the one measured.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

from harness import cell as CELL  # noqa: E402
from harness import jobs as J  # noqa: E402


def readings(cell, program, seed: int, n: int, control: bool, *,
             seconds: float = 1e9, generations=None, fault=None):
    from harness import check as CK
    from harness import faults as F
    data_seed = cell.config["assumed"]["dataset"]["seed"]
    search = J.Search(program, program.config(cell.config), cell.mix,
                      data_seed)
    with F.planted(fault) if fault else contextlib.nullcontext():
        window = search.run(J.Window(seconds, generations),
                            lambda j: J.derive(seed, J.STREAM_JOB0 + j))
    picked = CK.sample(window, n, seed)
    specs = [s for s, _ in picked]
    answers = [{"accuracy": r.accuracy, "area_mm2": r.area_mm2}
               for _, r in picked]
    ref = CK.reference_answers(cell.config, cell.mix, data_seed, specs)
    each = CK.per_answer(answers, ref)
    out = {"seed": seed, "generations": window.generations,
           "evaluations": window.evaluations, "sampled": len(picked),
           "program": CK.gaps(answers, ref), "per_candidate": each,
           "disagreeing": [s for s, g in zip(specs, each)
                           if max(g) > CK.AGREE]}
    if fault:
        out = dict(fault=fault, **out)
    if control:
        ctl = CK.control_answers(cell.config, cell.mix, data_seed, specs)
        out["control"] = CK.gaps(ctl, ref)
    return out


def summary(workload: str, rows) -> dict:
    """Per number: the largest sound reading, the smallest control and
    fault readings."""
    out = {"workload": workload,
           "seeds": len([r for r in rows if "fault" not in r])}
    sound = [r for r in rows if "fault" not in r]
    for k in rows[0]["program"]:
        out[k] = {"program_max": max(r["program"][k] for r in sound),
                  "control_min": min((r["control"][k] for r in sound
                                      if "control" in r), default=None)}
        for f in sorted({r["fault"] for r in rows if "fault" in r}):
            out[k][f + "_min"] = min(r["program"][k] for r in rows
                                     if r.get("fault") == f)
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--control-seeds", default="")
    p.add_argument("--faults", default="",
                   help="faults of harness/faults.py to plant, by name")
    p.add_argument("--fault-seeds", default="")
    p.add_argument("--seconds", type=float, default=None,
                   help="window length (default 20 s without --generations)")
    p.add_argument("--generations", type=int, default=None,
                   help="close each window after this many generations")
    p.add_argument("--sample", type=int, default=0,
                   help="evaluations compared per seed (default: as a run)")
    args = p.parse_args(argv)
    cell = CELL.load(args.workload)

    import jax

    import run as RUN
    from harness.device import NoChip, require_tpu
    from harness.program import Program
    RUN.configure_compile_cache(jax)
    try:
        require_tpu(jax.devices(), cell.chips)
    except NoChip as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 3
    program = Program()
    n = args.sample or cell.mix["check"]["sample"]
    seeds = [int(s) for s in args.seeds.split(",") if s]
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    faults = [f for f in args.faults.split(",") if f]
    fault_seeds = [int(s) for s in args.fault_seeds.split(",") if s]
    runs = [(s, None) for s in seeds + sorted(controls - set(seeds))] + \
        [(s, f) for f in faults for s in fault_seeds]
    seconds = args.seconds or (1e9 if args.generations else 20.0)
    rows = []
    for seed, fault in runs:
        t0 = time.perf_counter()
        r = readings(cell, program, seed, n,
                     fault is None and seed in controls,
                     seconds=seconds, generations=args.generations,
                     fault=fault)
        r["seconds"] = time.perf_counter() - t0
        rows.append(r)
        print(json.dumps(r), flush=True)
    print(json.dumps(summary(args.workload, rows)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
