"""Population evaluation: one spec (P = 1) against the same spec in a
population, vectorized CSD pricing, the bespoke launch, approximated
candidates in the packed launch, and the persistent evaluation cache."""
import dataclasses

import numpy as np
import pytest

from repro.configs.printed_mlp import PRINTED_MLPS
from repro.core import batch_eval as BE
from repro.core import hw_model as HW
from repro.core import minimize as MZ
from repro.core.compression_spec import LayerMin, ModelMin

CFG = PRINTED_MLPS["seeds"]          # smallest dataset: fastest finetunes
N_LAYERS = len(CFG.layer_dims) - 1

# a deliberately heterogeneous population: off/on per technique, mixed
# bits/sparsity/cluster counts, including the all-off baseline gene
SPECS = [
    ModelMin.uniform(N_LAYERS, bits=8),
    ModelMin.uniform(N_LAYERS, bits=3),
    ModelMin.uniform(N_LAYERS, bits=6, sparsity=0.4),
    ModelMin.uniform(N_LAYERS, bits=4, sparsity=0.3, clusters=4),
    ModelMin((LayerMin(2, 0.5, 2), LayerMin(8, 0.0, 16)), 8),
    ModelMin((LayerMin(5, 0.0, 3), LayerMin(4, 0.2, None)), 8),
]


# ---------------------------------------------------------------------------
# vectorized CSD / pricing
# ---------------------------------------------------------------------------


def test_csd_vec_matches_scalar_for_all_int8():
    coeffs = np.arange(-128, 128)
    vec = HW.csd_nonzero_digits_vec(coeffs)
    ref = np.array([HW.csd_nonzero_digits(int(c)) for c in coeffs])
    np.testing.assert_array_equal(vec, ref)


def test_csd_vec_wide_range_and_shapes():
    rng = np.random.default_rng(0)
    q = rng.integers(-(2 ** 15), 2 ** 15, (7, 11, 13))
    vec = HW.csd_nonzero_digits_vec(q)
    ref = np.array([HW.csd_nonzero_digits(int(c)) for c in q.reshape(-1)])
    np.testing.assert_array_equal(vec.reshape(-1), ref)


def test_mlp_cost_batch_matches_scalar_per_candidate():
    rng = np.random.default_rng(1)
    P_ = 5
    qs, bits, cls = [], [], []
    for (din, dout) in [(7, 8), (8, 3)]:
        q = rng.integers(-127, 128, (P_, din, dout))
        q[rng.random(q.shape) < 0.35] = 0
        idx = rng.integers(0, 4, (P_, din, dout))
        cb = rng.integers(-127, 128, (P_, din, 4))
        has = np.array([True, False, True, False, True])
        qs.append(q)
        bits.append(rng.integers(2, 9, P_))
        cls.append((idx, cb, has))
    batch = HW.mlp_cost_batch(qs, w_bits=bits, clusters=cls)
    for p in range(P_):
        clp = [(cls[i][0][p], cls[i][1][p]) if cls[i][2][p] else None
               for i in range(2)]
        ref = HW.mlp_cost([q[p] for q in qs],
                          w_bits=[int(b[p]) for b in bits], clusters=clp)
        assert batch["total_fa"][p] == ref.total_fa
        assert batch["n_multipliers"][p] == ref.n_multipliers
        assert batch["area_mm2"][p] == ref.area_mm2


# ---------------------------------------------------------------------------
# population-size invariance: each spec at P = 1 against P = N
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def serial_and_batched(own_pack):
    """Each spec alone (`evaluate_spec`, P = 1), and all in one call, each
    call scoring netlists it packed itself."""
    serial = [own_pack(MZ.evaluate_spec, CFG, s, epochs=30) for s in SPECS]
    batched = own_pack(BE.evaluate_population, CFG, SPECS, epochs=30)
    return serial, batched


def test_batched_objectives_match_serial(serial_and_batched):
    """A candidate's objectives do not depend on the population it is
    finetuned in: P = 1 against P = 6, within 1e-3."""
    serial, batched = serial_and_batched
    for s, b in zip(serial, batched):
        assert abs(s.accuracy - b.accuracy) <= 1e-3, s.spec
        assert abs(s.area_mm2 - b.area_mm2) <= 1e-3 * max(s.area_mm2, 1.0)
        assert s.n_multipliers == b.n_multipliers, s.spec
        assert abs(s.power_mw - b.power_mw) <= 1e-3 * max(s.power_mw, 1.0)


def test_batched_prices_mixed_input_bits_per_candidate(own_pack):
    """Regression: a population mixing input_bits must price each candidate
    at its own input width (prod_width = in_bits + w_bits drives every cost
    term), matching each candidate evaluated alone (P = 1)."""
    mixed = [ModelMin.uniform(N_LAYERS, bits=4, input_bits=4),
             ModelMin.uniform(N_LAYERS, bits=4, input_bits=8)]
    serial = [own_pack(MZ.evaluate_spec, CFG, s, epochs=10) for s in mixed]
    batched = own_pack(BE.evaluate_population, CFG, mixed, epochs=10)
    for s, b in zip(serial, batched):
        assert s.area_mm2 == b.area_mm2, s.spec
        assert s.power_mw == b.power_mw, s.spec
        assert abs(s.accuracy - b.accuracy) <= 1e-3, s.spec


@pytest.mark.parametrize("spec", [
    ModelMin.uniform(N_LAYERS, bits=6),
    ModelMin.uniform(N_LAYERS, bits=4, sparsity=0.3, clusters=4),
    ModelMin.uniform(N_LAYERS, bits=6, sparsity=0.2, csd_drop=1, lsb=2,
                     argmax_lsb=2),
], ids=["exact", "pruned_clustered", "approx"])
def test_evaluate_spec_is_the_population_of_one(spec, own_pack):
    """`evaluate_spec` is `evaluate_population` at P = 1: every field of
    the result equal, approximated specs included, each side scoring a
    netlist it packed itself."""
    one = own_pack(MZ.evaluate_spec, CFG, spec, epochs=10)
    assert one == own_pack(BE.evaluate_population, CFG, [spec],
                           epochs=10)[0]
    assert one.delay_levels is not None and one.delay_levels > 0


def test_compile_spec_is_the_evaluators_compile():
    """`circuit.compile_spec` returns the `CompiledMLP` that
    `minimize.compile_bespoke` gives inside `evaluate_population` for the
    same spec, and its netlist is that compile's."""
    from repro import circuit
    spec = ModelMin.uniform(N_LAYERS, bits=5, sparsity=0.3, clusters=3)
    seen = []
    orig = MZ.compile_bespoke

    def spy(params, s, masks, **kw):
        seen.append(orig(params, s, masks, **kw))
        return seen[-1]

    mp = pytest.MonkeyPatch()
    mp.setattr(MZ, "compile_bespoke", spy)
    try:
        BE.evaluate_population(CFG, [spec], epochs=10)
    finally:
        mp.undo()
    assert len(seen) == 1
    net, compiled = circuit.compile_spec(CFG, spec, epochs=10)
    _assert_same_compiled(compiled, seen[0], every_slot=True)
    assert len(net) == len(circuit.compile_netlist(seen[0]))


def test_batched_preserves_order_and_dedups(serial_and_batched):
    _, batched = serial_and_batched
    # duplicated spec evaluates once but appears at both positions
    dup = [SPECS[1], SPECS[0], SPECS[1]]
    out = BE.evaluate_population(CFG, dup, epochs=30)
    assert [r.spec for r in out] == dup
    assert out[0].accuracy == out[2].accuracy


def test_padded_kmeans_matches_static_k():
    """Valid-slot centroids/assignments of the padded dynamic-k k-means
    equal clustering's static-k path (the equivalence the engine rests on)."""
    import jax
    import jax.numpy as jnp
    from repro.core import clustering as C
    x = jax.random.normal(jax.random.PRNGKey(3), (40,))
    # every k the GA can emit (CLUSTER_CHOICES starts at 2; 0 bypasses the
    # cluster transform entirely, 1 never occurs)
    for k in (2, 3, 5, 8, 16):
        cent_ref, a_ref = C._kmeans_1d(x, k)
        cent, a = BE._padded_kmeans_1d(x, jnp.int32(k), BE.K_MAX)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(a_ref))
        np.testing.assert_allclose(np.asarray(cent[:k]),
                                   np.asarray(cent_ref), rtol=0, atol=0)


# ---------------------------------------------------------------------------
# the bespoke compile in one population launch
# ---------------------------------------------------------------------------

BITS_GENES = (None, 2, 3, 4, 5, 6, 7, 8)
CLUSTER_GENES = (None, 2, 3, 4, 6, 8, 12, 16)


def _serial_recipe(params, spec, masks):
    """The bespoke compile as it was before the population launch: an eager
    `cluster_per_input` or `quantize_int` per layer, then the host snap."""
    import jax.numpy as jnp
    from repro.core import clustering as C
    from repro.core import quantization as Q
    q_layers, scales, biases, clusters, w_bits = [], [], [], [], []
    for i, layer in enumerate(params["layers"]):
        lm = spec.layers[i]
        bits = lm.bits if lm.bits is not None else 8
        w = np.asarray(layer["w"], np.float32)
        if masks[i] is not None:
            w = w * np.asarray(masks[i], np.float32)
        if lm.clusters is not None:
            cb, idx = C.cluster_per_input(jnp.asarray(w), lm.clusters)
            cb, idx = np.asarray(cb), np.asarray(idx)
            w_rec = np.take_along_axis(cb, idx, axis=1)
            qmax = 2 ** (bits - 1) - 1
            s = max(np.abs(w_rec).max(), 1e-8) / qmax
            cb_q = np.clip(np.round(cb / s), -qmax, qmax).astype(np.int64)
            q = np.take_along_axis(cb_q, idx, axis=1)
            if masks[i] is not None:
                q = q * np.asarray(masks[i], np.int64)
            clusters.append((idx, cb_q))
        else:
            qj, sj = Q.quantize_int(jnp.asarray(w), Q.QuantConfig(bits=bits))
            q, s = np.asarray(qj, np.int64), float(np.asarray(sj))
            clusters.append(None)
        q_layers.append(q)
        scales.append(float(s))
        biases.append(np.asarray(layer["b"], np.float32))
        w_bits.append(bits)
    return MZ.CompiledMLP(q_layers, scales, biases, clusters, w_bits,
                          spec.input_bits)


def _bespoke_population(dims, n_real, seed=0):
    """A seeded trained-population stand-in at ``dims``, padded to its
    power-of-two bucket as the evaluator pads: every bits and cluster gene
    on every layer, sparsity 0 and 0.3. -> (params with the bucket axis,
    specs, per-candidate masks, the launch's fetched outputs)."""
    import jax
    rng = np.random.default_rng(seed)
    n_layers = len(dims) - 1
    specs = [ModelMin(tuple(
        LayerMin(BITS_GENES[(p + 3 * i) % 8], (0.0, 0.3)[(p + i) % 2],
                 CLUSTER_GENES[(3 * p + i) % 8]) for i in range(n_layers)),
        4) for p in range(n_real)]
    bucket = 1 << (n_real - 1).bit_length()
    padded = specs + [specs[-1]] * (bucket - n_real)
    params = {"layers": tuple(
        {"w": rng.normal(size=(bucket, a, b)).astype(np.float32),
         "b": rng.normal(size=(bucket, b)).astype(np.float32)}
        for a, b in zip(dims[:-1], dims[1:]))}
    masks = [[np.asarray(MZ.P.magnitude_mask(layer["w"][p],
                                             s.layers[i].sparsity), bool)
              if s.layers[i].sparsity > 0 else None
              for i, layer in enumerate(params["layers"])]
             for p, s in enumerate(padded)]
    stacked = tuple(np.stack([np.ones(layer["w"].shape[1:], np.float32)
                              if m[i] is None else m[i].astype(np.float32)
                              for m in masks])
                    for i, layer in enumerate(params["layers"]))
    bits, ks = BE.stack_specs(padded)
    out = BE._population_bespoke(params, bits, ks, stacked,
                                 k_max=BE._bespoke_k_max(ks))
    params, out = jax.tree_util.tree_map(lambda a: a[:n_real],
                                         jax.device_get((params, out)))
    return params, specs, masks[:n_real], out


def _candidate(tree, p):
    import jax
    return jax.tree_util.tree_map(lambda a: a[p], tree)


def _assert_same_compiled(got, want, *, every_slot):
    """Exact equality of two `CompiledMLP`s. With ``every_slot=False`` the
    integer codebooks are compared on the slots some weight indexes, the
    only ones the netlist and the pricing read: a slot no weight takes
    keeps its quantile initialisation, which the static-k and the padded
    k-means interpolate a few ulps apart, and a value on a half of the grid
    can then round either way."""
    assert got.w_bits == want.w_bits
    assert got.scales == want.scales
    assert got.input_bits == want.input_bits
    for a, b in zip(got.q_layers + got.biases, want.q_layers + want.biases):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(a, b)
    for a, b in zip(got.clusters, want.clusters):
        assert (a is None) == (b is None)
        if a is None:
            continue
        (ia, ca), (ib, cb) = a, b
        assert ia.dtype == ib.dtype and ca.dtype == cb.dtype
        np.testing.assert_array_equal(ia, ib)
        assert ca.shape == cb.shape
        read = np.ones(ca.shape, bool)
        if not every_slot:
            read = np.zeros(ca.shape, bool)
            np.put_along_axis(read, ia, True, axis=1)
        np.testing.assert_array_equal(ca[read], cb[read])


@pytest.mark.parametrize("dims", [(11, 4, 7), (16, 5, 10)])
def test_population_bespoke_matches_serial_recipe(dims):
    """The population launch plus the host snap gives the integers the
    eager per-layer recipe gave, and P = 1 gives what P = n gives."""
    params, specs, masks, out = _bespoke_population(dims, n_real=13)
    for p, spec in enumerate(specs):
        pp = _candidate(params, p)
        got = MZ.compile_bespoke(pp, spec, masks[p],
                                 prefetched=_candidate(out, p))
        _assert_same_compiled(got, _serial_recipe(pp, spec, masks[p]),
                              every_slot=False)
        _assert_same_compiled(MZ.compile_bespoke(pp, spec, masks[p]), got,
                              every_slot=True)


def test_prefetched_bespoke_stays_on_the_host():
    """The per-candidate compile from the launch's outputs moves nothing
    between host and device; ``eval.bespoke_prefetched`` counts it once
    per candidate, in the evaluator too, and never on the serial path."""
    import jax
    from repro.obs import metrics as MT
    prefetched = MT.counter("eval.bespoke_prefetched")
    params, specs, masks, out = _bespoke_population((11, 4, 7), n_real=6)
    before = prefetched.value
    with jax.transfer_guard("disallow"):
        for p, spec in enumerate(specs):
            MZ.compile_bespoke(_candidate(params, p), spec, masks[p],
                               prefetched=_candidate(out, p))
    assert prefetched.value - before == len(specs)

    before = prefetched.value
    MZ.compile_bespoke(_candidate(params, 0), specs[0], masks[0])
    assert prefetched.value == before

    evaluated = MT.counter("eval.specs_evaluated")
    before, n_before = prefetched.value, evaluated.value
    BE.evaluate_population(CFG, SPECS + SPECS[:2], epochs=30)
    assert evaluated.value - n_before == len(SPECS)
    assert prefetched.value - before == len(SPECS)


# ---------------------------------------------------------------------------
# approximated candidates in the population's one netlist launch
# ---------------------------------------------------------------------------

# WhiteWine at its published 11-4-7: accuracy is not saturated there, so a
# candidate scored on another netlist shows. Exact and approximated
# genomes interleaved: every approximation gene, alone and together, on
# clustered, pruned and plain layers
WW = dataclasses.replace(PRINTED_MLPS["whitewine"], hidden=(4,))
APPROX_SPECS = [
    ModelMin.uniform(2, bits=4, sparsity=0.4, clusters=8, csd_drop=1, lsb=2),
    ModelMin.uniform(2, bits=8),
    ModelMin.uniform(2, bits=6, csd_drop=2),
    ModelMin.uniform(2, bits=4, sparsity=0.3, clusters=4),
    ModelMin.uniform(2, bits=8, lsb=3, argmax_lsb=4),
    ModelMin((LayerMin(5, 0.2, None, csd_drop=1),
              LayerMin(4, 0.0, 3, lsb=1)), 8, argmax_lsb=2),
    ModelMin((LayerMin(5, 0.0, 3), LayerMin(4, 0.2, None)), 8),
]


@pytest.fixture(scope="module")
def approx_population():
    """One `evaluate_population` call over the mixed population, with the
    arguments its `_compile_and_price` got (the trained population and
    the bespoke launch's outputs) and the counters it moved."""
    from repro.obs import metrics as MT
    seen = {}
    orig = BE._compile_and_price

    def capture(params_pop, specs, masks, xte, yte, **kw):
        seen.update(args=(params_pop, specs, masks, xte, yte), kw=kw)
        return orig(params_pop, specs, masks, xte, yte, **kw)

    names = ("netlist_sim.launches", "eval.approx_packed")
    before = {n: MT.counter(n).value for n in names}
    BE._compile_and_price = capture
    try:
        rs = BE.evaluate_population(WW, APPROX_SPECS, epochs=10)
    finally:
        BE._compile_and_price = orig
    moved = {n: MT.counter(n).value - before[n] for n in names}
    return rs, seen, moved


def test_approx_fold_matches_one_candidate_launches(approx_population):
    """Scoring approximated candidates in the population's packed launch
    gives each candidate what a launch of its own gives, and what the
    serial `approx.evaluate_netlist` gives: every field equal."""
    import jax
    from repro import approx as AX
    from repro import circuit as CIRC
    rs, seen, _ = approx_population
    params_pop, specs, masks, xte, yte = seen["args"]
    bespoke = seen["kw"]["bespoke"]
    assert [r.spec for r in rs] == specs == APPROX_SPECS
    folded = BE._compile_and_price(params_pop, specs, masks, xte, yte,
                                   bespoke=bespoke)
    assert folded == rs
    for p, spec in enumerate(specs):
        def one(tree, p=p):
            return jax.tree_util.tree_map(lambda a: a[p:p + 1], tree)
        alone = BE._compile_and_price(one(params_pop), [spec], masks[p:p + 1],
                                      xte, yte, bespoke=one(bespoke))
        assert alone == [folded[p]], spec
        if spec.has_approx:
            c = MZ.compile_bespoke(_candidate(params_pop, p), spec, masks[p],
                                   prefetched=_candidate(bespoke, p))
            serial = AX.evaluate_netlist(CIRC.compile_netlist(c), c, spec,
                                         xte, yte)
            assert serial == folded[p], spec


def test_approx_candidates_share_the_one_launch(approx_population):
    """k approximated and m exact candidates: one engine launch, k counted
    as packed; an all-exact population counts none."""
    from repro.obs import metrics as MT
    _, _, moved = approx_population
    k = sum(s.has_approx for s in APPROX_SPECS)
    assert 0 < k < len(APPROX_SPECS)
    assert moved == {"netlist_sim.launches": 1, "eval.approx_packed": k}

    launches = MT.counter("netlist_sim.launches")
    packed = MT.counter("eval.approx_packed")
    n0, k0 = launches.value, packed.value
    BE.evaluate_population(WW, [s for s in APPROX_SPECS if not s.has_approx],
                           epochs=10)
    assert (launches.value - n0, packed.value - k0) == (1, 0)


# ---------------------------------------------------------------------------
# persistent cache
# ---------------------------------------------------------------------------


def test_eval_cache_roundtrip(tmp_path):
    cache = BE.EvalCache(tmp_path / "evals.json")
    r = MZ.EvalResult(SPECS[3], 0.912, 1234.5, 6.7, 89)
    cache.put(CFG.name, 0, 30, r)
    cache.flush()

    fresh = BE.EvalCache(tmp_path / "evals.json")   # re-read from disk
    assert len(fresh) == 1
    hit = fresh.get(CFG.name, 0, 30, SPECS[3])
    assert hit is not None
    assert hit.spec == SPECS[3]
    assert hit.accuracy == pytest.approx(0.912)
    assert hit.area_mm2 == pytest.approx(1234.5)
    assert hit.n_multipliers == 89
    # different seed / epochs / spec are misses
    assert fresh.get(CFG.name, 1, 30, SPECS[3]) is None
    assert fresh.get(CFG.name, 0, 31, SPECS[3]) is None
    assert fresh.get(CFG.name, 0, 30, SPECS[0]) is None


def test_cache_two_writers_merge_on_flush(tmp_path):
    """Two processes sharing one cache file must union their entries:
    flush re-reads the on-disk JSON before the atomic replace, so a
    writer no longer clobbers what a concurrent writer published."""
    path = tmp_path / "shared.json"
    a = BE.EvalCache(path)
    b = BE.EvalCache(path)              # opened before a writes anything
    a.put(CFG.name, 0, 30, MZ.EvalResult(SPECS[0], 0.9, 100.0, 1.0, 10,
                                         delay_levels=15))
    a.flush()
    b.put(CFG.name, 0, 30, MZ.EvalResult(SPECS[1], 0.8, 50.0, 0.5, 5,
                                         delay_levels=12))
    b.flush()                           # must not drop a's entry

    merged = BE.EvalCache(path)
    assert len(merged) == 2
    assert merged.get(CFG.name, 0, 30, SPECS[0]).area_mm2 == 100.0
    assert merged.get(CFG.name, 0, 30, SPECS[1]).area_mm2 == 50.0
    # on a key conflict the flushing writer's (fresher) entry wins
    a2 = BE.EvalCache(path)
    a2.put(CFG.name, 0, 30, MZ.EvalResult(SPECS[0], 0.95, 99.0, 1.0, 10,
                                          delay_levels=15))
    a2.flush()
    assert BE.EvalCache(path).get(CFG.name, 0, 30,
                                  SPECS[0]).area_mm2 == 99.0


def test_cache_roundtrips_delay_and_separates_netlist_keyspace(tmp_path):
    cache = BE.EvalCache(tmp_path / "evals.json")
    r = MZ.EvalResult(SPECS[0], 0.9, 100.0, 1.0, 10, delay_levels=17)
    cache.put(CFG.name, 0, 30, r)
    cache.flush()
    hit = BE.EvalCache(tmp_path / "evals.json").get(CFG.name, 0, 30,
                                                    SPECS[0])
    assert hit.delay_levels == 17
    # netlist-exact results live under their own keys (different objective)
    assert cache.get(CFG.name, 0, 30, SPECS[0], netlist=True) is None
    cache.put(CFG.name, 0, 30,
              MZ.EvalResult(SPECS[0], 0.89, 100.0, 1.0, 10,
                            delay_levels=17), netlist=True)
    assert cache.get(CFG.name, 0, 30,
                     SPECS[0], netlist=True).accuracy == pytest.approx(0.89)
    assert cache.get(CFG.name, 0, 30,
                     SPECS[0]).accuracy == pytest.approx(0.9)


def test_cache_size_cap_evicts_lru_on_flush(tmp_path):
    """A long GA sweep must not grow the on-disk cache without bound:
    flush keeps only the ``max_entries`` most recently touched entries."""
    path = tmp_path / "capped.json"
    cache = BE.EvalCache(path, max_entries=3)
    for i, s in enumerate(SPECS[:5]):
        cache.put(CFG.name, 0, 30, MZ.EvalResult(s, 0.9, 100.0 + i, 1.0, 10))
    # refresh the OLDEST entry: a hit keeps it young through eviction
    assert cache.get(CFG.name, 0, 30, SPECS[0]) is not None
    cache.flush()

    fresh = BE.EvalCache(path, max_entries=3)
    assert len(fresh) == 3
    assert fresh.get(CFG.name, 0, 30, SPECS[0]) is not None   # refreshed
    assert fresh.get(CFG.name, 0, 30, SPECS[4]) is not None   # newest
    assert fresh.get(CFG.name, 0, 30, SPECS[1]) is None       # evicted
    assert fresh.get(CFG.name, 0, 30, SPECS[2]) is None


def test_cache_recency_only_flush_is_batched(tmp_path):
    """A warm (hit-only) flush below the touch threshold is a no-op (no
    multi-MB rewrite per cached generation); past the threshold the
    refreshed stamps do persist."""
    path = tmp_path / "warm.json"
    cache = BE.EvalCache(path)
    cache.put(CFG.name, 0, 30, MZ.EvalResult(SPECS[0], 0.9, 1.0, 1.0, 1))
    cache.flush()
    before = path.read_text()

    warm = BE.EvalCache(path)
    warm.get(CFG.name, 0, 30, SPECS[0])
    warm.flush()                          # few touches: skipped
    assert path.read_text() == before
    for _ in range(BE.EvalCache.TOUCH_FLUSH_EVERY):
        warm.get(CFG.name, 0, 30, SPECS[0])
    warm.flush()                          # batched recency persists
    assert path.read_text() != before


def test_cache_cap_survives_merge_and_uncapped_by_default(tmp_path):
    path = tmp_path / "merged.json"
    a = BE.EvalCache(path, max_entries=2)
    b = BE.EvalCache(path, max_entries=2)
    a.put(CFG.name, 0, 30, MZ.EvalResult(SPECS[0], 0.9, 1.0, 1.0, 1))
    a.flush()
    b.put(CFG.name, 0, 30, MZ.EvalResult(SPECS[1], 0.9, 2.0, 1.0, 1))
    b.put(CFG.name, 0, 30, MZ.EvalResult(SPECS[2], 0.9, 3.0, 1.0, 1))
    b.flush()                     # merge of 3 entries, capped back to 2
    assert len(BE.EvalCache(path)) == 2
    # max_entries=None disables the cap entirely
    big = BE.EvalCache(tmp_path / "uncapped.json", max_entries=None)
    for i, s in enumerate(SPECS):
        big.put(CFG.name, 0, 30, MZ.EvalResult(s, 0.9, float(i), 1.0, 1))
    big.flush()
    assert len(BE.EvalCache(tmp_path / "uncapped.json")) == len(SPECS)


def test_cache_corrupt_file_salvaged_with_backup(tmp_path):
    """A truncated cache file (crash mid-write, disk-full) must not cost
    the whole cache: `_read` backs the damaged bytes up to `.corrupt` and
    salvages every individually-parseable leading entry."""
    path = tmp_path / "evals.json"
    cache = BE.EvalCache(path)
    for i, s in enumerate(SPECS[:4]):
        cache.put(CFG.name, 0, 30, MZ.EvalResult(s, 0.9, float(i), 1.0, 1,
                                                 delay_levels=10 + i))
    cache.flush()
    whole = path.read_text()
    # tear the file mid-way through the last entry's value
    path.write_text(whole[:int(len(whole) * 0.8)])

    with pytest.warns(UserWarning, match="salvaged"):
        torn = BE.EvalCache(path)
    assert path.with_suffix(".json.corrupt").read_text() == \
        whole[:int(len(whole) * 0.8)]
    # every complete leading entry survived, the torn tail did not
    assert 1 <= len(torn) < 4
    hit = torn.get(CFG.name, 0, 30, SPECS[0])
    assert hit is not None and hit.area_mm2 == 0.0 and hit.delay_levels == 10
    # the next flush atomically rewrites a whole file again
    torn.put(CFG.name, 0, 30, MZ.EvalResult(SPECS[4], 0.9, 9.0, 1.0, 1))
    torn.flush()
    assert len(BE.EvalCache(path)) == len(torn)


def test_cache_unparseable_garbage_starts_empty(tmp_path):
    path = tmp_path / "evals.json"
    path.write_text("not json at all")
    with pytest.warns(UserWarning, match="salvaged 0 entries"):
        cache = BE.EvalCache(path)
    assert len(cache) == 0
    assert path.with_suffix(".json.corrupt").exists()


def test_cache_skips_retraining(tmp_path, monkeypatch):
    cache = BE.EvalCache(tmp_path / "evals.json")
    specs = SPECS[:2]
    first = BE.evaluate_population(CFG, specs, epochs=25, cache=cache)
    assert len(cache) == 2

    # a fully-cached population must never touch the finetune engine
    def boom(*a, **k):
        raise AssertionError("finetune ran on a fully-cached population")
    monkeypatch.setattr(BE, "_population_finetune", boom)
    again = BE.evaluate_population(CFG, specs, epochs=25, cache=cache)
    for a, b in zip(first, again):
        assert a.accuracy == b.accuracy and a.area_mm2 == b.area_mm2


def test_approx_scores_read_no_earlier_pack(approx_population):
    """An approximated candidate is scored on the netlist its own finetune
    gave, whatever the pack cache holds from an earlier evaluation of the
    same genome: under a different finetune (half the rows) a warm cache
    and an empty one give it the same result."""
    orig = BE._population_finetune

    def half_rows(params0, bits, ks, masks, x, y, **kw):
        n = x.shape[0] // 2
        return orig(params0, bits, ks, masks, x[:n], y[:n], **kw)

    BE._population_finetune = half_rows
    try:
        warm = BE.evaluate_population(WW, APPROX_SPECS, epochs=10)
        saved = dict(BE._PACK_CACHE)
        BE._PACK_CACHE.clear()
        try:
            cold = BE.evaluate_population(WW, APPROX_SPECS, epochs=10)
        finally:
            BE._PACK_CACHE.update(saved)
    finally:
        BE._population_finetune = orig
    clean = approx_population[0]
    for w, c, r in zip(warm, cold, clean):
        if w.spec.has_approx:
            assert w == c, w.spec
    # the planted finetune does move the approximated answers
    assert any(w.accuracy != r.accuracy
               for w, r in zip(warm, clean) if w.spec.has_approx)
