"""Fault-injection suite for the island-model search runtime.

The acceptance invariants of the fault-tolerance PR, asserted bit-for-bit:

* **resume equivalence** — a search preempted (killed) after ANY round and
  resumed from its checkpoint produces a byte-identical final Pareto front
  to the uninterrupted run, on real datasets with the real batched QAT
  evaluator, with ZERO evaluations re-run;
* **island kill** — a worker death mid-generation loses no completed
  evaluation and never stalls the survivors;
* **evaluation exception** — a failing spec (injected OverflowError / NaN
  accuracy) is retried once, then quarantined with worst-case fitness and
  a structured record, instead of aborting the generation;
* **torn cache file** — a truncated on-disk EvalCache is salvaged entry by
  entry (damaged bytes backed up), and the search recovers with zero
  evaluations redone.
"""
import shutil

import numpy as np
import pytest

from repro.configs.printed_mlp import PRINTED_MLPS
from repro.core import batch_eval as BE
from repro.core.compression_spec import ModelMin
from repro.core.ga import GAConfig, run_nsga2
from repro.search import (IslandConfig, PreemptedError, SearchConfig,
                          SearchRuntime)
from repro.search.faults import (EvalFault, FaultHarness, FaultPlan,
                                 inject_eval_faults)

EPOCHS = 2          # QAT epochs: enough to exercise the full real pipeline
SEED = 0
DATASETS = ("seeds", "redwine")


def _search_cfg(ds: str, rounds: int = 4) -> SearchConfig:
    cfg = PRINTED_MLPS[ds]
    return SearchConfig(
        n_layers=len(cfg.layer_dims) - 1,
        rounds=rounds,
        ga=GAConfig(population=4, seed=5, input_bits=cfg.input_bits),
        islands=IslandConfig(n_islands=2, migration_every=2, migrants=1))


def _evaluator(ds: str, cache_dir, quarantine=None):
    cache = BE.EvalCache(cache_dir / f"{ds}.json")
    return BE.make_batch_evaluator(PRINTED_MLPS[ds], epochs=EPOCHS,
                                   seed=SEED, cache=cache,
                                   quarantine=quarantine), cache


@pytest.fixture(scope="module")
def cache_dir(tmp_path_factory):
    return tmp_path_factory.mktemp("eval_caches")


@pytest.fixture(scope="module")
def baselines(cache_dir):
    """Uninterrupted searches, one per dataset — the ground truth every
    faulted/resumed run must reproduce byte-for-byte."""
    out = {}
    for ds in DATASETS:
        be, cache = _evaluator(ds, cache_dir)
        rt = SearchRuntime(_search_cfg(ds), batch_evaluate=be,
                           eval_cache=cache)
        out[ds] = rt.run()
    return out


def _assert_same_front(res, base):
    assert [s.to_json() for s in res.front_specs] == \
        [s.to_json() for s in base.front_specs]
    np.testing.assert_array_equal(res.front_objectives,
                                  base.front_objectives)
    assert res.evaluations == base.evaluations


def _count_real_evals(monkeypatch):
    """Every spec reaching `_compile_and_price` paid a real QAT finetune —
    cache hits never get there. The zero-evaluations-lost assertions count
    through this."""
    evaluated = []
    orig = BE._compile_and_price

    def counting(params_pop, specs, *a, **kw):
        evaluated.extend(s.to_json() for s in specs)
        return orig(params_pop, specs, *a, **kw)

    monkeypatch.setattr(BE, "_compile_and_price", counting)
    return evaluated


# ---------------------------------------------------------------------------
# resume equivalence (simulated preemption) — real evaluator, 2 datasets
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("ds", DATASETS)
@pytest.mark.parametrize("kill_round", [0, 1, 2])
def test_preempt_resume_bit_identical(ds, kill_round, cache_dir, baselines,
                                      tmp_path, monkeypatch):
    base = baselines[ds]
    be, cache = _evaluator(ds, cache_dir)
    harness = FaultHarness(FaultPlan(preempt_at=kill_round))
    rt = SearchRuntime(_search_cfg(ds), batch_evaluate=be,
                       ckpt_root=tmp_path, harness=harness,
                       eval_cache=cache)
    with pytest.raises(PreemptedError):
        rt.run()
    assert rt.mgr.latest_step() == kill_round + 1   # preemption flushed

    # "new process": fresh evaluator + fresh cache handle over the same
    # on-disk state; count real finetunes from here on — must be zero
    # (nothing lost to the kill, nothing re-evaluated on resume)
    evaluated = _count_real_evals(monkeypatch)
    be2, cache2 = _evaluator(ds, cache_dir)
    rt2 = SearchRuntime.resume(_search_cfg(ds), tmp_path,
                               batch_evaluate=be2, eval_cache=cache2)
    res = rt2.run()
    _assert_same_front(res, base)
    assert evaluated == []


def test_resume_without_checkpoint_raises(tmp_path):
    with pytest.raises(FileNotFoundError):
        SearchRuntime.resume(_search_cfg("seeds"), tmp_path / "empty",
                             evaluate=lambda s: (0.5, 1.0))


# ---------------------------------------------------------------------------
# island kill — worker death mid-generation
# ---------------------------------------------------------------------------


def _synthetic(spec):
    bits = sum(l.bits for l in spec.layers)
    sp = sum(l.sparsity for l in spec.layers)
    return (bits / 16.0, sp)


def _synthetic_cfg(rounds=4, islands=3):
    return SearchConfig(
        n_layers=2, rounds=rounds,
        ga=GAConfig(population=6, seed=3),
        islands=IslandConfig(n_islands=islands, migration_every=2,
                             migrants=1))


def test_island_kill_loses_no_completed_evaluation():
    harness = FaultHarness(FaultPlan(kill_island={1: 1}))
    rt = SearchRuntime(_synthetic_cfg(), evaluate=_synthetic,
                       harness=harness)
    res = rt.run()
    gens = [st.generation for st in res.islands]
    # island 1 finished round 0, died mid-round-1 (rolled back), survivors
    # ran all 4 rounds
    assert gens == [4, 1, 4]
    kill_events = [e for e in res.events if e["event"] == "killed"]
    assert len(kill_events) == 1 and kill_events[0]["island"] == 1
    assert harness.log == [("kill", 1, 1)]
    # zero completed evaluations lost: everything the dead island ever
    # evaluated (its whole committed population) is still in the merged
    # result and counted for the front
    for spec in res.islands[1].population:
        assert spec.to_json() in res.evaluations
    # deterministic under the same fault plan
    res2 = SearchRuntime(_synthetic_cfg(), evaluate=_synthetic,
                         harness=FaultHarness(
                             FaultPlan(kill_island={1: 1}))).run()
    _assert_same_front(res2, res)


def test_all_islands_killed_raises():
    harness = FaultHarness(FaultPlan(kill_island={0: 0, 1: 0, 2: 0}))
    rt = SearchRuntime(_synthetic_cfg(), evaluate=_synthetic,
                       harness=harness)
    with pytest.raises(RuntimeError, match="every island is dead"):
        rt.run()


def test_kill_then_preempt_then_resume_keeps_dead_island_dead(tmp_path):
    plan = FaultPlan(kill_island={1: 1}, preempt_at=2)
    rt = SearchRuntime(_synthetic_cfg(), evaluate=_synthetic,
                       ckpt_root=tmp_path, harness=FaultHarness(plan))
    with pytest.raises(PreemptedError):
        rt.run()
    rt2 = SearchRuntime.resume(_synthetic_cfg(), tmp_path,
                               evaluate=_synthetic)
    res = rt2.run()
    assert [st.generation for st in res.islands] == [4, 1, 4]
    # the faulted-run ground truth: same plan, no preemption
    ref = SearchRuntime(_synthetic_cfg(), evaluate=_synthetic,
                        harness=FaultHarness(
                            FaultPlan(kill_island={1: 1}))).run()
    _assert_same_front(res, ref)


# ---------------------------------------------------------------------------
# evaluation exceptions — retry, quarantine, structured diagnostics
# ---------------------------------------------------------------------------

QSPECS = [ModelMin.uniform(2, bits=8), ModelMin.uniform(2, bits=3),
          ModelMin.uniform(2, bits=5, sparsity=0.3)]


@pytest.fixture(scope="module")
def clean_results():
    cfg = PRINTED_MLPS["seeds"]
    return BE.evaluate_population(cfg, QSPECS, epochs=EPOCHS, seed=SEED)


def test_deterministic_eval_fault_quarantined(clean_results):
    cfg = PRINTED_MLPS["seeds"]
    bad = QSPECS[1].to_json()
    q = []
    with inject_eval_faults([EvalFault(spec_json=bad, fail_attempts=2)]):
        rs = BE.evaluate_population(cfg, QSPECS, epochs=EPOCHS, seed=SEED,
                                    quarantine=q)
    # the failing spec got worst-case fitness, not a crashed generation
    assert rs[1].accuracy == 0.0
    assert rs[1].area_mm2 == BE.QUARANTINE_AREA_MM2
    assert rs[1].delay_levels == BE.QUARANTINE_DELAY_LEVELS
    # structured diagnostics
    assert len(q) == 1
    rec = q[0]
    assert rec.spec_json == bad
    assert rec.error == "OverflowError"
    assert rec.attempts == 2
    assert "netlist sim budget" in rec.message
    # bystanders are untouched — byte-identical to the clean run
    for i in (0, 2):
        assert rs[i] == clean_results[i]


def test_transient_eval_fault_absorbed_by_retry(clean_results):
    cfg = PRINTED_MLPS["seeds"]
    bad = QSPECS[0].to_json()
    q = []
    with inject_eval_faults([EvalFault(spec_json=bad,
                                       fail_attempts=1)]) as hook:
        rs = BE.evaluate_population(cfg, QSPECS, epochs=EPOCHS, seed=SEED,
                                    quarantine=q)
    assert hook.triggered == [(bad, 1)]     # the fault really fired
    assert q == []                          # ...and the retry absorbed it
    assert rs[0] == clean_results[0]


def test_nan_accuracy_quarantined(monkeypatch):
    from repro.core import minimize as MZ
    cfg = PRINTED_MLPS["seeds"]
    monkeypatch.setattr(MZ, "compiled_accuracy",
                        lambda c, x, y: float("nan"))
    q = []
    # the NaN guard belongs to the analytic scorer (an integer-argmax
    # netlist accuracy cannot come back NaN), so opt out of the default
    rs = BE.evaluate_population(cfg, [QSPECS[0]], epochs=EPOCHS, seed=SEED,
                                quarantine=q, netlist=False)
    assert rs[0].accuracy == 0.0
    assert len(q) == 1
    assert q[0].stage == "score"
    assert q[0].error == "FloatingPointError"
    assert "NaN accuracy" in q[0].message


def test_quarantined_specs_never_cached(tmp_path):
    cfg = PRINTED_MLPS["seeds"]
    cache = BE.EvalCache(tmp_path / "c.json")
    bad = QSPECS[1].to_json()
    with inject_eval_faults([EvalFault(spec_json=bad, fail_attempts=2)]):
        BE.evaluate_population(cfg, QSPECS, epochs=EPOCHS, seed=SEED,
                               cache=cache, quarantine=[])
    # healthy specs cached (under the default netlist-exact keyspace),
    # the quarantined one left for a fixed toolchain
    assert cache.get(cfg.name, SEED, EPOCHS, QSPECS[0],
                     netlist=True) is not None
    assert cache.get(cfg.name, SEED, EPOCHS, QSPECS[1], netlist=True) is None


def test_quarantine_surfaces_in_ga_result():
    """A GA search whose every evaluation fails still completes, with the
    records surfaced on GAResult.quarantined."""
    cfg = PRINTED_MLPS["seeds"]
    q = []
    be = BE.make_batch_evaluator(cfg, epochs=EPOCHS, seed=SEED,
                                 quarantine=q)
    with inject_eval_faults([EvalFault(fail_attempts=2)]):   # every spec
        res = run_nsga2(2, None,
                        GAConfig(population=4, generations=1, seed=0),
                        batch_evaluate=be, quarantine=q)
    assert len(res.quarantined) > 0
    assert all(r.attempts == 2 for r in res.quarantined)
    # worst-case fitness everywhere: acc objective 1.0, area penalty
    assert np.all(res.objectives[:, 0] == 1.0)
    assert np.all(res.objectives[:, 1] == BE.QUARANTINE_AREA_MM2)


# approximated candidates are scored in the population's one netlist
# launch with the exact ones: a fault in one still quarantines it alone
AXSPECS = [ModelMin.uniform(2, bits=5, csd_drop=1, lsb=2), QSPECS[0],
           ModelMin.uniform(2, bits=6, sparsity=0.3, argmax_lsb=4),
           ModelMin.uniform(2, bits=4, clusters=4, csd_drop=2)]


@pytest.fixture(scope="module")
def clean_approx_results():
    return BE.evaluate_population(PRINTED_MLPS["seeds"], AXSPECS,
                                  epochs=EPOCHS, seed=SEED)


def _assert_only_quarantined(rs, clean, bad_index):
    assert rs[bad_index].accuracy == 0.0
    assert rs[bad_index].area_mm2 == BE.QUARANTINE_AREA_MM2
    for i, r in enumerate(rs):
        if i != bad_index:
            assert r == clean[i], AXSPECS[i]


@pytest.mark.parametrize("fail_attempts", [1, 2])
def test_approx_eval_fault_isolated_in_shared_launch(fail_attempts,
                                                     clean_approx_results):
    bad = AXSPECS[2].to_json()
    q = []
    with inject_eval_faults([EvalFault(spec_json=bad,
                                       fail_attempts=fail_attempts)]) as hook:
        rs = BE.evaluate_population(PRINTED_MLPS["seeds"], AXSPECS,
                                    epochs=EPOCHS, seed=SEED, quarantine=q)
    assert hook.triggered == [(bad, a) for a in range(1, fail_attempts + 1)]
    if fail_attempts == 1:                  # the retry absorbs it
        assert q == []
        assert rs == clean_approx_results
    else:
        assert [(r.spec_json, r.stage) for r in q] == [(bad, "compile")]
        _assert_only_quarantined(rs, clean_approx_results, 2)


def test_raising_approx_pass_quarantined_at_score(monkeypatch,
                                                  clean_approx_results):
    """A pass that raises for one candidate's genes quarantines that
    candidate at ``score``; the approximated bystanders still share the
    launch and come out byte-identical."""
    from repro.approx import budget
    bad = AXSPECS[3]
    target = budget.ApproxParams.from_spec(bad)
    orig = budget.build_passes

    def build_passes(p):
        if p == target:
            raise OverflowError("injected: pass failed")
        return orig(p)

    monkeypatch.setattr(budget, "build_passes", build_passes)
    q = []
    rs = BE.evaluate_population(PRINTED_MLPS["seeds"], AXSPECS,
                                epochs=EPOCHS, seed=SEED, quarantine=q)
    assert [(r.spec_json, r.stage, r.attempts) for r in q] == \
        [(bad.to_json(), "score", 2)]
    _assert_only_quarantined(rs, clean_approx_results, 3)


# ---------------------------------------------------------------------------
# torn cache file
# ---------------------------------------------------------------------------


def test_torn_cache_salvaged_and_search_recovers(cache_dir, baselines,
                                                 tmp_path, monkeypatch):
    """Truncate the on-disk EvalCache mid-search: the next flush salvages
    the readable entries, backs the damaged bytes up to `.corrupt`, and
    the search finishes with a bit-identical front and zero re-runs."""
    ds = "seeds"
    # clean 3-round ground truth (pure cache replay of the baseline run)
    be, cache = _evaluator(ds, cache_dir)
    ref = SearchRuntime(_search_cfg(ds, rounds=3), batch_evaluate=be,
                        eval_cache=cache).run()

    # private copy of the warm cache that the harness will tear
    torn_path = tmp_path / "torn.json"
    shutil.copy(cache_dir / f"{ds}.json", torn_path)
    evaluated = _count_real_evals(monkeypatch)
    # a fully-warm replay batches its recency-only flushes; force them
    # eager so the first flush after the tear re-reads (and salvages) disk
    monkeypatch.setattr(BE.EvalCache, "TOUCH_FLUSH_EVERY", 1)
    cache2 = BE.EvalCache(torn_path)
    be2 = BE.make_batch_evaluator(PRINTED_MLPS[ds], epochs=EPOCHS,
                                  seed=SEED, cache=cache2)
    harness = FaultHarness(FaultPlan(tear_cache_at=2),
                           cache_path=torn_path)
    rt = SearchRuntime(_search_cfg(ds, rounds=3), batch_evaluate=be2,
                       harness=harness, eval_cache=cache2)
    res = rt.run()
    assert any(ev[0] == "tear_cache" for ev in harness.log)
    _assert_same_front(res, ref)
    assert evaluated == []                    # zero evaluations redone
    # the damaged bytes were preserved for post-mortem...
    assert torn_path.with_suffix(".json.corrupt").exists()
    # ...and the rewritten cache is whole again: a fresh reader sees every
    # entry the in-memory cache knew
    assert len(BE.EvalCache(torn_path)) == len(cache2)
