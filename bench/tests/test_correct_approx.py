"""``correct`` in the approximation cell, beyond `test_correct.py`'s cases:
each part of the approximation left out of the timed path reads
``correct: false``, and the sample those runs compare holds every
approximation gene, so that a part left out has something to change.

The runs are `test_correct.py`'s: ``bench/run.py``'s ``main`` with the
look for a chip skipped, the mix cut to population 6 and 2 generations at
the cell's own 90 epochs, the window to 4 generations, under the limits
committed for the chip.
"""
import json

import pytest
from test_correct import GENERATIONS, SEEDS, _result, small  # noqa: F401

from harness import cell as CELL
from harness import check as CK
from harness import faults as F
from harness import jobs as J

CELL_NAME = "whitewine-approx-p14"


def test_sample_holds_every_gene(small):
    from harness.program import Program
    program = Program()
    cell = CELL.load(CELL_NAME)
    seed = SEEDS[CELL_NAME]
    search = J.Search(program, program.config(cell.config), cell.mix,
                      cell.config["assumed"]["dataset"]["seed"])
    window = search.run(J.Window(1000.0),
                        lambda j: J.derive(seed, J.STREAM_JOB0 + j))
    assert window.generations == GENERATIONS
    specs = [json.loads(s) for s, _ in
             CK.sample(window, cell.mix["check"]["sample"], seed)]
    layers = [l for s in specs for l in s["layers"]]
    assert any(l.get("csd_drop") for l in layers)
    assert any(l.get("lsb") for l in layers)
    assert any(s.get("argmax_lsb") for s in specs)


@pytest.mark.parametrize("left_out", ["csd", "lsb", "argmax"])
def test_left_out_approximation_is_not_correct(small, capsys, left_out):
    from harness.program import Program
    Program()
    with F.planted(left_out):
        out = _result(capsys, CELL_NAME)
    assert out["correct"] is False, out["check"]
