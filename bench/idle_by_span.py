"""Name the device's idle time in a traced run by the program's spans.

    python3 bench/idle_by_span.py --workload <name> [--top 10]

Reads what ``bench/run.py --trace 1`` left under
``artifacts/bench/<workload>/``: the program's span records and the
profiler trace, where every program span is also a host-plane annotation.
Prints one JSON object: ``idle_by_span`` ([[span, idle seconds], ...],
most first; ``null`` is idle time under no program span), ``idle_s`` and
``named_share`` (the share of the window's device-idle seconds under a
program span), and ``span_records`` (the window's span records).
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

from harness import programspans as PS  # noqa: E402
from harness import spans as SP  # noqa: E402
from harness import xtrace as XT  # noqa: E402
from harness.cell import ROOT  # noqa: E402


def summarize(art: Path, top: int = 10):
    records = SP.in_window(art / "spans.jsonl")
    names = {r["name"] for r in records if r.get("kind") == "span"}
    xplane = XT.find_xplane(art / "xprof")
    trace = XT.load(xplane)
    lo, hi = trace.window()
    every = PS.idle_by_span(trace, PS.host_spans(xplane, names), lo, hi,
                            n=None)
    idle = sum(s for _, s in every)
    named = sum(s for k, s in every if k is not None)
    return {"idle_by_span": every[:top], "idle_s": idle,
            "named_share": named / idle if idle else None,
            "span_records": sum(r.get("kind") == "span" for r in records)}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--top", type=int, default=10)
    args = p.parse_args(argv)
    art = ROOT / "artifacts" / "bench" / args.workload
    print(json.dumps(summarize(art, args.top)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
