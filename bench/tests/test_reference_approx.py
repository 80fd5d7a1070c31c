"""The reference's approximated circuit (`reference/approx.py`) against
the program's approximation passes, at whitewine size with few epochs.

Both sides start from the program's own fixed-point compile of each
genome, so the comparison isolates the approximation: the program's
approximated netlist, simulated and priced structurally, against the
reference's integer forward and price. Accuracy must agree exactly, area
to 1e-9 relative; a genome without approximation genes must take the
reference's exact path and give the program's analytic answers.
"""
import json

import pytest

from harness import cell as CELL
from harness import jobs as J
from harness.program import Program
from reference import approx as AX
from reference import printed_mlp as PM

EPOCHS = 20
BASES = [{"bits": 4}, {"bits": 6, "sparsity": 0.5},
         {"bits": 5, "sparsity": 0.3, "clusters": 4}]
ALONE = ([("csd_drop", d) for d in (1, 2, 3)]
         + [("lsb", k) for k in (1, 2, 3, 4, 6)]
         + [("argmax_lsb", k) for k in (2, 4, 6, 8)])


def _spec(program, base, **genes):
    am = genes.pop("argmax_lsb", 0)
    return program.ModelMin.uniform(2, input_bits=8, argmax_lsb=am,
                                    **base, **genes)


@pytest.fixture(scope="module")
def evaluated():
    """{group: [(spec JSON, program answer, program's compile)]}."""
    program = Program()
    cell = CELL.load("whitewine-approx-p14")
    cfg = program.config(cell.config)
    groups = {f"{g}={v}": [_spec(program, b, **{g: v}) for b in BASES]
              for g, v in ALONE}
    search = J.Search(program, cfg, cell.mix, 0)
    groups["mixed"] = search.random_specs(24, 20261018)
    exact = J.Search(program, cfg, dict(cell.mix, genes=dict(
        cell.mix["genes"], csd_drop=[0], lsb=[0], argmax_lsb=[0])), 0)
    groups["exact"] = exact.seed_specs[:3] + exact.random_specs(6, 7)
    specs = [s for g in groups.values() for s in g]

    from repro.core import minimize as MZ
    compiled = {}
    orig = MZ.compile_bespoke

    def spy(params, spec, masks, **kw):
        c = orig(params, spec, masks, **kw)
        compiled[spec.to_json()] = c
        return c

    mp = pytest.MonkeyPatch()
    mp.setattr(MZ, "compile_bespoke", spy)
    try:
        results = program.BE.evaluate_population(
            cfg, specs, epochs=EPOCHS, seed=0, cache=None, netlist=True)
    finally:
        mp.undo()
    by_spec = {s.to_json(): r for s, r in zip(specs, results)}
    data = cell.config["assumed"]["dataset"]
    _, _, xte, yte = PM.make_dataset(data, data["seed"])
    return {g: [(s.to_json(), by_spec[s.to_json()], compiled[s.to_json()])
                for s in ss] for g, ss in groups.items()}, xte, yte


def _comp(c):
    return list(zip(c.q_layers, c.scales, c.biases, c.clusters, c.w_bits))


def _agree(program, reference):
    assert program.accuracy == reference["accuracy"]
    assert abs(program.area_mm2 - reference["area_mm2"]) \
        <= 1e-9 * reference["area_mm2"]
    assert program.n_multipliers == reference["n_multipliers"]


@pytest.mark.parametrize("group", [f"{g}={v}" for g, v in ALONE] + ["mixed"])
def test_approximated_circuit_matches_program(evaluated, group):
    groups, xte, yte = evaluated
    for spec_json, answer, c in groups[group]:
        spec = json.loads(spec_json)
        genes = AX.genes(spec)
        assert genes is not None, spec_json
        comp = _comp(c)
        ib = spec["input_bits"]
        ref = AX.evaluate(comp, ib, genes, PM.integer_inputs(xte, ib),
                          PM.integer_biases(comp, ib), yte)
        _agree(answer, ref)


def test_exact_genomes_take_todays_path(evaluated):
    groups, xte, yte = evaluated
    for spec_json, answer, c in groups["exact"]:
        spec = json.loads(spec_json)
        assert AX.genes(spec) is None, spec_json
        comp = _comp(c)
        ref = PM.price(comp, spec["input_bits"])
        ref["accuracy"] = PM.integer_accuracy(comp, spec["input_bits"], xte,
                                              yte)
        _agree(answer, ref)


def test_csd_truncation_matches_program():
    from repro.approx.passes import truncate_csd
    from repro.core.hw_model import csd_digits
    for c in list(range(-300, 301)):
        assert AX.csd_digits(c) == csd_digits(c)
        for drop in range(4):
            if c:
                assert AX.truncate_csd(c, drop) == truncate_csd(c, drop)


def test_product_interval_matches_lowering():
    from repro.circuit import compile as CC
    from repro.circuit import ir
    for c in [c for c in range(-130, 131) if c]:
        for lo, hi in [(0, 255), (0, 0), (3, 3), (7, 900)]:
            net = ir.Netlist(in_bits=8, w_bits=[8])
            x = net.input(0)
            net.nodes[x].lo, net.nodes[x].hi = lo, hi
            root = CC._lower_const_mult(net, x, c, layer=0, unit=(0, 0))
            n = net.nodes[root]
            assert AX.product_interval(c, lo, hi) == (n.lo, n.hi)
            assert AX.width(n.lo, n.hi) == n.width
