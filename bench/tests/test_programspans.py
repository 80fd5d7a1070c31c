"""The readers of the program's per-layer spans, on hand-built records,
and the device's idle time named by host-plane program spans."""
import pytest

from harness import programspans as PS
from harness import xtrace as XT
from harness.jobs import Window
from harness.readers import Artifacts, read


def _span(name, dur, id_, parent=None):
    return {"kind": "span", "name": name, "ts": 0.0, "dur": dur,
            "depth": 0, "id": id_, "parent": parent}


# two generations: every duration below is per window, so a reader's
# per-generation milliseconds are half of it times 1000
RECORDS = [
    _span("eval.prepare", 0.002, 1, 0),
    _span("eval.finetune", 0.010, 2, 0),
    _span("eval.fetch", 0.001, 3, 0),
    _span("minimize.compile_bespoke", 0.004, 5, 4),
    _span("circuit.compile", 0.006, 6, 4),
    _span("minimize.compile_bespoke", 0.004, 7, 4),
    _span("circuit.compile", 0.006, 8, 4),
    _span("netlist_sim.pack", 0.003, 9, 4),
    _span("kernels.netlist_sim.levels", 0.001, 11, 10),
    _span("kernels.netlist_sim", 0.002, 10, 4),
    _span("eval.price", 0.005, 12, 4),
    _span("eval.compile_price", 0.032, 4, 0),
    _span("eval.population", 0.050, 0),
    _span("ga.generation", 0.060, 13),
    {"kind": "event", "name": "netlist_sim.padding", "ts": 0.0,
     "attrs": {"lanes_used": 30, "lanes_total": 40}},
    {"kind": "event", "name": "netlist_sim.padding", "ts": 0.0,
     "attrs": {"lanes_used": 10, "lanes_total": 40}},
]


def _artifacts(records, generations=2):
    w = Window(1.0, generations=generations)
    return Artifacts(window=w, trace=None, lo=0.0, hi=1e9, spans=records,
                     config={}, mix={}, peaks={}, train_rows=0, compiles=0)


@pytest.mark.parametrize("name, want", [
    ("eval_prep_host_ms", 1.0),
    ("finetune_host_ms", 5.5),
    ("bespoke_host_ms", 4.0),
    ("netlist_build_host_ms", 6.0),
    ("pack_host_ms", 1.5),
    ("netlist_host_ms", 1.0),
    ("pricing_host_ms", 2.5),
    # 0.050 less its direct children 0.002 + 0.010 + 0.001 + 0.032; the
    # grandchildren under eval.compile_price are not subtracted again
    ("eval_self_host_ms", 2.5),
    ("netlist_lane_pct", 50.0),
])
def test_reader_known_answer(name, want):
    assert read(name, _artifacts(RECORDS)) == pytest.approx(want)


@pytest.mark.parametrize("name", [
    "eval_prep_host_ms", "finetune_host_ms", "bespoke_host_ms",
    "netlist_build_host_ms", "pack_host_ms", "netlist_host_ms",
    "pricing_host_ms", "eval_self_host_ms", "netlist_lane_pct"])
def test_reader_finds_nothing_in_a_program_without_the_spans(name):
    # a program older than the span: records without ids or the new names
    old = [{"kind": "span", "name": "eval.compile_price", "ts": 0.0,
            "dur": 0.03, "depth": 1}]
    assert read(name, _artifacts(old)) is None
    assert read(name, _artifacts([])) is None


def test_finetune_host_needs_both_spans():
    no_fetch = [r for r in RECORDS if r["name"] != "eval.fetch"]
    assert read("finetune_host_ms", _artifacts(no_fetch)) is None


def test_self_time_joins_children_by_parent_not_name():
    recs = [_span("a", 0.010, 0), _span("a", 0.004, 1, 0),
            _span("b", 0.001, 2, 1)]
    # outer 10 - inner 4 (its child), inner 4 - 1
    assert PS.self_s(recs, "a") == pytest.approx(0.006 + 0.003)


def _trace():
    # window [0, 100] ns; device 0 busy [10, 40] u [60, 70]: idle
    # [0, 10], [40, 60], [70, 100]
    ops = {0: [(10, 40, "fusion.1"), (60, 70, "copy.2")]}
    notes = [(0, 100, "bench.window"), (0, 100, "bench.generation"),
             (5, 95, "bench.evaluate")]
    return XT.Trace(ops, [], notes)          # still builds by position


def test_idle_by_span_innermost_program_span():
    spans = [(0, 80, "eval.population"), (35, 65, "eval.compile_price"),
             (45, 55, "circuit.compile")]
    got = dict(map(tuple, PS.idle_by_span(_trace(), spans, 0, 100, n=None)))
    # [0,10] population; [40,45] and [55,60] compile_price; [45,55]
    # circuit.compile; [70,80] population; [80,100] under bench.* only
    assert got == {"eval.population": pytest.approx(20e-9),
                   "eval.compile_price": pytest.approx(10e-9),
                   "circuit.compile": pytest.approx(10e-9),
                   None: pytest.approx(20e-9)}
    assert sum(got.values()) == pytest.approx(60e-9)
    top = PS.idle_by_span(_trace(), spans, 0, 100, n=2)
    assert {k for k, _ in top} == {"eval.population", None}


def test_idle_by_span_bench_annotations_name_nothing():
    got = PS.idle_by_span(_trace(), [], 0, 100)
    assert got == [[None, pytest.approx(60e-9)]]
    assert PS.idle_by_span(XT.Trace({}, [], []), [], 0, 100) == []


def test_idle_by_span_clips_to_window():
    spans = [(-50, 20, "eval.prepare"), (90, 200, "eval.price")]
    got = dict(map(tuple, PS.idle_by_span(_trace(), spans, 0, 100, n=None)))
    assert got["eval.prepare"] == pytest.approx(10e-9)
    assert got["eval.price"] == pytest.approx(10e-9)


def test_host_spans_reads_program_spans_off_a_profile(tmp_path):
    import jax
    import jax.numpy as jnp

    from repro.obs import trace as TR
    with TR.capture(tmp_path / "spans.jsonl"):
        with jax.profiler.trace(str(tmp_path / "xprof")):
            with TR.span("eval.population"):
                with TR.span("eval.prepare"):
                    jnp.ones(4).block_until_ready()
    xplane = XT.find_xplane(tmp_path / "xprof")
    got = PS.host_spans(xplane, {"eval.population", "eval.prepare"})
    (outer,) = [x for x in got if x[2] == "eval.population"]
    (inner,) = [x for x in got if x[2] == "eval.prepare"]
    assert outer[0] <= inner[0] <= inner[1] <= outer[1]
    assert PS.host_spans(xplane, ()) == []
