"""``correct`` in the Seeds cell: a sound run passes, the control and each
fault of the exact path fail, and the cell's configuration is the
program's own Seeds classifier.

The runs are `test_correct.py`'s: ``bench/run.py``'s ``main`` with the
look for a chip skipped, the mix cut to population 6 and 2 generations at
the cell's own 90 epochs, the window to 4 generations, under the limits
committed for the chip. Accuracy here moves in steps of 1/52 (52 test
rows), so a single altered answer reads above the ``acc_gap`` limit.
"""
import dataclasses

import pytest
from test_correct import GENERATIONS, _result, small  # noqa: F401

from harness import cell as CELL
from harness import check as CK
from harness import faults as F

CELL_NAME = "seeds-exact-p14"


def test_config_is_the_programs_seeds():
    from repro.configs.printed_mlp import PRINTED_MLPS
    from repro.data.uci import SPECS
    config = CELL.load(CELL_NAME).config
    data = {k: v for k, v in config["assumed"]["dataset"].items()
            if k not in ("seed", "note")}
    assert data == dataclasses.asdict(SPECS["seeds"])
    assert tuple(config["layer_dims"]) == PRINTED_MLPS["seeds"].layer_dims
    assert config["assumed"]["topology"]["layer_dims"] == \
        config["layer_dims"]


def test_sound_run_is_correct(small, capsys):
    out = _result(capsys, CELL_NAME)
    assert out["correct"] is True, out["check"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"evals_per_s", "gen_s", "setup_s"}
    assert set(out["check"]) == set(CK.limited(CELL.load(CELL_NAME).limits))


def test_acc_gap_limit_is_under_one_answer():
    limits = CELL.load(CELL_NAME).limits
    assert 0 < limits["acc_gap"] < 1 / 52


def test_control_fails():
    """The reference in bfloat16, in the program's place, fails a limit."""
    import calibrate
    from harness.program import Program
    cell = CELL.load(CELL_NAME)
    cell.mix = dict(cell.mix, population=6, generations=2)
    r = calibrate.readings(cell, Program(), 3000000043, 6, True,
                           generations=GENERATIONS)
    assert CK.judge(r["program"], cell.limits)[0]
    assert not CK.judge(r["control"], cell.limits)[0]


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "answer_altered"])
def test_broken_timed_path_is_not_correct(small, capsys, fault):
    from harness.program import Program
    Program()
    with F.planted(fault):
        out = _result(capsys, CELL_NAME)
    assert out["correct"] is False, out["check"]
