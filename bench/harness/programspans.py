"""The program's own spans (``repro.obs.trace``), read two ways.

* From the window's JSONL records (``harness.spans.in_window``): host
  seconds inside spans of a name, and a span's self time -- its duration
  less that of its direct children, joined by the records' ``parent``.
* From the profiler trace, where every program span is also a host-plane
  annotation on the device's clock: `host_spans` loads them, and
  `idle_by_span` sums the device's idle time by the innermost program span
  that covers it.
"""
from __future__ import annotations

import bisect
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Sequence

from harness.xtrace import Interval, Trace, merge


def _spans(records: Sequence[Dict], name: str) -> List[Dict]:
    return [r for r in records
            if r.get("kind") == "span" and r.get("name") == name]


def span_s(records: Sequence[Dict], *names: str) -> Optional[float]:
    """Seconds inside spans of the given names, summed; None unless each
    name has a span (a program without the span has nothing to read)."""
    total = 0.0
    for name in names:
        found = _spans(records, name)
        if not found:
            return None
        total += sum(r["dur"] for r in found)
    return total


def self_s(records: Sequence[Dict], name: str) -> Optional[float]:
    """Seconds inside spans of ``name`` that none of their direct children
    cover; None when no such span carries an ``id``."""
    own = [r for r in _spans(records, name) if "id" in r]
    if not own:
        return None
    ids = {r["id"] for r in own}
    children = sum(r["dur"] for r in records if r.get("kind") == "span"
                   and r.get("parent") in ids)
    return sum(r["dur"] for r in own) - children


def host_spans(xplane: Path, names: Iterable[str]) -> List[Interval]:
    """(start_ns, end_ns, name) of each host-plane event named in
    ``names``, on the trace's clock."""
    from jax.profiler import ProfileData
    keep = set(names)
    pd = ProfileData.from_file(str(xplane))
    return [(e.start_ns, e.end_ns, e.name)
            for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events if e.name in keep]


def _idle(busy, lo: float, hi: float):
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def idle_by_span(trace: Trace, spans: Sequence[Interval], lo: float,
                 hi: float, n: Optional[int] = 10) -> List[List]:
    """Device-0 idle seconds in [lo, hi], summed by the innermost span of
    ``spans`` over each idle instant (the latest-started one open there):
    [[name, seconds], ...], most first, ``n`` of them (all with None).
    Idle time under no span is summed under the name None."""
    if not trace.ops:
        return []
    gaps = _idle(merge(trace.ops[min(trace.ops)], lo, hi), lo, hi)
    starts = [s for s, _ in gaps]
    before = [0.0]
    for s, e in gaps:
        before.append(before[-1] + e - s)

    def idle_to(t: float) -> float:
        i = bisect.bisect_right(starts, t) - 1
        if i < 0:
            return 0.0
        return before[i] + min(t, gaps[i][1]) - gaps[i][0]

    clipped = [(max(s, lo), min(e, hi), name) for s, e, name in spans
               if min(e, hi) > max(s, lo)]
    edges = sorted([(s, 1, j) for j, (s, _, _) in enumerate(clipped)]
                   + [(e, -1, j) for j, (_, e, _) in enumerate(clipped)])
    edges.append((hi, 0, -1))
    open_: set = set()
    out: Dict[Optional[str], float] = {}
    t = lo
    for when, kind, j in edges:
        if when > t:
            name = (clipped[max(open_, key=lambda k: (clipped[k][0],
                                                      -clipped[k][1]))][2]
                    if open_ else None)
            out[name] = out.get(name, 0.0) + (idle_to(when) - idle_to(t)) / 1e9
            t = when
        if kind == 1:
            open_.add(j)
        elif kind == -1:
            open_.discard(j)
    ranked = sorted(out.items(), key=lambda kv: -kv[1])
    return [[k, v] for k, v in (ranked if n is None else ranked[:n])]
