"""``correct`` at a size the CPU holds: a sound run passes, the control
fails, and so does a run with the timed path broken underneath.

The runs go through ``bench/run.py``'s ``main`` with the look for a chip
skipped, the mix cut to population 6 and 2 generations at the cell's
own 90 epochs and the window to 4 generations; the cell's limits are the
ones committed for the chip. The faults are `harness/faults.py`'s.
"""
import functools
import json

import pytest

import run as RUN
from harness import cell as CELL
from harness import check as CK
from harness import device as DEV
from harness import faults as F
from harness import jobs as J

CELLS = ["whitewine-exact-p14", "pendigits-exact-p14", "whitewine-approx-p14"]
# the cells the broken timed path is tried in: one per kind of search
BROKEN = ["whitewine-exact-p14", "whitewine-approx-p14"]
# the window of a CPU run: two whole jobs, whatever the machine's speed
GENERATIONS = 4
WARM = {"nodes": [128, 128], "waves": [16, 16]}
# the approximation cell's seed draws a sample that holds every gene
SEEDS = {"whitewine-approx-p14": 3000000045}


@pytest.fixture
def small(monkeypatch):
    load = CELL.load

    def small_cell(name):
        c = load(name)
        c.mix = dict(c.mix, population=6, generations=2, netlist_warmup=WARM)
        return c

    monkeypatch.setattr(CELL, "load", small_cell)
    monkeypatch.setattr(J, "Window", functools.partial(
        J.Window, max_generations=GENERATIONS))
    monkeypatch.setattr(DEV, "require_tpu", lambda devices, chips: {
        "platform": devices[0].platform, "kind": "TPU v5 lite",
        "count": chips})
    monkeypatch.setattr(DEV, "memory_peak_bytes", lambda devices: 0)


def _result(capsys, workload):
    seed = SEEDS.get(workload, 3000000041)
    rc = RUN.main(["--workload", workload, "--seed", str(seed),
                   "--seconds", "1000", "--trace", "0"])
    assert rc == 0
    line = capsys.readouterr().out.strip().splitlines()[-1]
    out = json.loads(line)
    assert list(out)[-1] == "check"
    return out


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct(small, capsys, workload):
    out = _result(capsys, workload)
    assert out["correct"] is True, out["check"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"evals_per_s", "gen_s", "setup_s"}
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert set(out["check"]) == set(CK.limited(CELL.load(workload).limits))


@pytest.mark.parametrize("workload", CELLS)
def test_control_fails(workload):
    """The reference in bfloat16, in the program's place, fails a limit."""
    import calibrate
    from harness.program import Program
    cell = CELL.load(workload)
    cell.mix = dict(cell.mix, population=6, generations=2)
    r = calibrate.readings(cell, Program(), 3000000043, 6, True,
                           generations=GENERATIONS)
    assert CK.judge(r["program"], cell.limits)[0]
    assert not CK.judge(r["control"], cell.limits)[0]


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "answer_altered"])
@pytest.mark.parametrize("workload", BROKEN)
def test_broken_timed_path_is_not_correct(small, capsys, workload, fault):
    from harness.program import Program
    Program()
    with F.planted(fault):
        out = _result(capsys, workload)
    assert out["correct"] is False, out["check"]


def test_limits_name_the_compared_numbers():
    ref = [{"accuracy": 0.5, "area_mm2": 100.0}] * 4
    ans = [dict(ref[0], area_mm2=101.0)] + ref[1:]
    found = CK.gaps(ans, ref)
    assert found == {"acc_gap": 0.0, "area_gap": 0.01,
                     "disagree_share": 0.25}
    ok, numbers = CK.judge(found, {"disagree_share": 0.3, "readings": {}})
    assert ok and numbers == {"disagree_share": {"value": 0.25,
                                                 "limit": 0.3}}
    assert not CK.judge(found, {"area_gap": 0.005, "acc_gap": 0.02})[0]
    for wrong in ({"disagree_gap": 0.3}, {"readings": {}}):
        with pytest.raises(KeyError):
            CK.judge(found, wrong)
