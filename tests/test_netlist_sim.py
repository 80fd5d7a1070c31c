"""Population-batched netlist simulation (`repro.kernels.netlist_sim`):
packing round-trips, padded mixed-size populations, bit-exactness of the
`levels` engine against `circuit.simulate` and the NumPy oracle, lane-width
selection off the verifier's per-node bounds, and the population-size and
fault wiring in `core.batch_eval`."""
import jax
import numpy as np
import pytest

from repro import circuit
from repro.circuit import ir
from repro.circuit.simulate import Simulator
from repro.configs.printed_mlp import PRINTED_MLPS
from repro.core import batch_eval as BE
from repro.core import minimize as MZ
from repro.core.compression_spec import ModelMin
from repro.kernels import netlist_sim as NS
from repro.kernels.netlist_sim import ops as NSO
from repro.verify.netlist import max_sim_width

from _hypothesis_compat import given, settings, st
from test_circuit import synth_compiled

RNG = np.random.default_rng(7)


def _synth_net(dims, bits=5, *, sparsity=0.0, clusters=None, seed=0):
    c = synth_compiled(dims, bits, sparsity=sparsity, clusters=clusters,
                       seed=seed)
    return circuit.compile_netlist(c)


def _assert_candidate_matches_serial(out, p, net, x):
    serial = Simulator(net).run(x)
    assert np.array_equal(out["argmax"][p], serial["argmax"])
    # exact netlists: the comparator operands ARE the output logits
    assert np.array_equal(out["amx"][p], serial["logits"].astype(np.int64))


# ---------------------------------------------------------------------------
# packing
# ---------------------------------------------------------------------------


def _assert_round_trip(pop, p, net):
    rt = NS.unpack_netlist(pop, p)
    assert set(rt) == set(range(len(net.nodes)))
    for nid, (o, args, sh, v) in rt.items():
        nd = net.nodes[nid]
        assert o == int(nd.op)
        if nd.op == ir.Op.ARGMAX:
            assert args == tuple(nd.args)
        elif nd.op in (ir.Op.SHL, ir.Op.TRUNC):
            assert args == (nd.args[0],) and sh == nd.shift
        elif nd.op in (ir.Op.ADD, ir.Op.SUB):
            assert args == tuple(nd.args)
        elif nd.op in (ir.Op.NEG, ir.Op.RELU):
            assert args == (nd.args[0],)
        elif nd.op == ir.Op.CONST:
            assert v == nd.value


@settings(max_examples=15, deadline=None)
@given(st.integers(min_value=0, max_value=10 ** 6))
def test_pack_unpack_round_trip(seed):
    """pack -> unpack reproduces every node's (op, args, shift, value) on
    randomized architectures — a lossy packer would silently simulate a
    different circuit."""
    r = np.random.default_rng(seed)
    dims = (int(r.integers(3, 10)), int(r.integers(3, 12)),
            int(r.integers(2, 6)))
    net = _synth_net(dims, int(r.integers(2, 7)),
                     sparsity=float(r.uniform(0.0, 0.6)),
                     clusters=int(r.integers(2, 6)) if r.random() < 0.5
                     else None,
                     seed=seed % 997)
    small = _synth_net((dims[0], 3, dims[-1]), 3, seed=seed % 991)
    pop = NS.pack_population([net, small])     # padded stacking too
    _assert_round_trip(pop, 0, net)
    _assert_round_trip(pop, 1, small)


def test_pack_rejects_mixed_arity():
    a = _synth_net((5, 4, 3))
    b = _synth_net((6, 4, 3))
    with pytest.raises(ValueError, match="mixed arities"):
        NS.pack_population([a, b])


# ---------------------------------------------------------------------------
# the engine: bit-exactness
# ---------------------------------------------------------------------------


def test_padded_population_mixed_sizes_bit_exact():
    """Candidates of very different node counts share one launch; each is
    bit-exact vs its own serial simulation and the NumPy oracle."""
    nets = [_synth_net(d, 5, seed=i) for i, d in enumerate(
        [(7, 3, 3), (7, 28, 3), (7, 14, 14, 3), (7, 5, 3)])]
    sizes = [len(n) for n in nets]
    assert max(sizes) / min(sizes) > 3     # genuinely mixed-size launch
    pop = NS.pack_population(nets)
    x = RNG.integers(0, 2 ** 4, size=(23, 7)).astype(np.int64)
    ref = NS.simulate_population_ref(pop, x)
    lev = NS.simulate_population(pop, x)
    for p, net in enumerate(nets):
        _assert_candidate_matches_serial(ref, p, net, x)
        assert np.array_equal(lev["amx"][p], ref["amx"][p])
        assert np.array_equal(lev["argmax"][p], ref["argmax"][p])


def test_small_window_many_waves_bit_exact():
    """A tiny wave width forces multi-wave levels (the schedule's chunking
    path) without changing results."""
    nets = [_synth_net((6, 9, 4), 4, seed=s) for s in (0, 1)]
    pop = NS.pack_population(nets)
    x = RNG.integers(0, 2 ** 4, size=(11, 6)).astype(np.int64)
    wide = NS.simulate_population(pop, x, window=512)
    narrow = NS.simulate_population(pop, x, window=8)
    assert np.array_equal(wide["amx"], narrow["amx"])


def test_batch_tiling_bit_exact():
    """B larger than block_b splits into padded tiles that reuse one
    executable; results are unchanged."""
    net = _synth_net((5, 6, 3), 4, seed=2)
    pop = NS.pack_population([net])
    x = RNG.integers(0, 2 ** 4, size=(37, 5)).astype(np.int64)
    whole = NS.simulate_population(pop, x, block_b=2048)
    tiled = NS.simulate_population(pop, x, block_b=16)
    assert np.array_equal(whole["amx"], tiled["amx"])


@pytest.mark.parametrize("dataset", ["seeds", "redwine", "whitewine",
                                     "pendigits"])
def test_population_engine_bit_exact_on_dataset(dataset):
    """The packed engine is bit-exact against `circuit.simulate.simulate`
    on real compiled candidates of all four paper datasets, and against the
    NumPy oracle on the cheap case."""
    cfg = PRINTED_MLPS[dataset]
    n = len(cfg.layer_dims) - 1
    _, (_, _, xte, _) = MZ.pretrain(cfg, seed=0)
    specs = [ModelMin.uniform(n, bits=8),
             ModelMin.uniform(n, bits=4, sparsity=0.3)]
    nets, xs = [], []
    for s in specs:
        net, c = circuit.compile_spec(cfg, s, epochs=10)
        nets.append(net)
        xs.append(np.asarray(MZ.quantize_inputs(c, xte[:256]), np.int64))
    pop = NS.pack_population(nets)
    out = NS.simulate_population(pop, np.stack(xs))
    for p, net in enumerate(nets):
        serial = circuit.simulate(net, xs[p])    # the acceptance oracle
        assert np.array_equal(out["argmax"][p], serial["argmax"])
        assert np.array_equal(out["amx"][p],
                              serial["logits"].astype(np.int64))
    if dataset == "seeds":                       # oracle parity, cheap case
        ref = NS.simulate_population_ref(pop, np.stack(xs))
        assert np.array_equal(ref["amx"], out["amx"])


# ---------------------------------------------------------------------------
# lane widths (satellite: per-node verifier bounds, not whole-net max)
# ---------------------------------------------------------------------------


def _width32_net():
    """Hand-built net whose widest word is exactly width 32 (int32 range):
    255 << 23 = 2139095040 <= 2^31 - 1."""
    net = ir.Netlist(in_bits=8, w_bits=[8])
    a = net.shl(net.input(0), 23)
    b = net.shl(net.input(1), 23)
    net.layer_pre_ids = [[a, b]]
    net.output_ids = [a, b]
    net.argmax([a, b])
    return net


def _lane(pop, x):
    """The lane dtype the `levels` engine picks for ``pop``, from the last
    element of its launch's executable key."""
    _, stats = NSO._run_engine(pop, np.asarray(x, np.int64)[None]
                               .repeat(pop.n_candidates, 0), 256, 2048)
    return stats["key"][-1]


def test_width32_net_stays_int32_and_bit_exact():
    """Width-32 words fit int32 exactly; the old whole-net `> 31` check
    promoted them to 64-bit lanes. Bit-exactness holds on the int32 path
    in the serial simulator, the population engine and the oracle."""
    net = _width32_net()
    assert max_sim_width(net) == 32
    assert net.max_width > 31              # the old rule would go int64
    sim = Simulator(net)
    assert sim._x64 is False               # the fix: int32 lanes
    x = np.array([[255, 200], [1, 255], [0, 0], [254, 255]], np.int64)
    got = sim.run(x)
    expect = np.stack([x[:, 0] << 23, x[:, 1] << 23], axis=1)
    assert np.array_equal(got["logits"].astype(np.int64), expect)
    pop = NS.pack_population([net])
    assert pop.max_width == 32
    assert _lane(pop, x) == "int32"
    lev = NS.simulate_population(pop, x)
    ref = NS.simulate_population_ref(pop, x)
    assert np.array_equal(lev["amx"][0], expect)
    assert np.array_equal(ref["amx"][0], expect)
    assert np.array_equal(lev["argmax"][0], got["argmax"])


def test_wide_population_takes_int64_lanes():
    """Past width 32 the levels engine runs int64 lanes, still bit-exact
    against the serial simulator and the oracle."""
    net = _synth_net((11, 12, 12, 7), 8, seed=3)
    pop = NS.pack_population([net])
    assert pop.max_width > 32
    x = RNG.integers(0, 2 ** 8, size=(9, 11)).astype(np.int64)
    assert _lane(pop, x) == "int64"
    lev = NS.simulate_population(pop, x)
    _assert_candidate_matches_serial(lev, 0, net, x)
    assert np.array_equal(NS.simulate_population_ref(pop, x)["amx"],
                          lev["amx"])


# ---------------------------------------------------------------------------
# batch_eval wiring: default objective, cache keys, fault propagation
# ---------------------------------------------------------------------------


def test_evalcache_keys_byte_stable():
    """Flipping the default objective must not move a single byte of the
    cache keyspace: analytic entries keep their historical keys, netlist
    entries their "|netlist" suffix."""
    s = ModelMin.uniform(2, bits=4)
    base = f"seeds|seed=0|epochs=30|{s.to_json()}"
    assert BE.EvalCache.key("seeds", 0, 30, s) == base
    assert BE.EvalCache.key("seeds", 0, 30, s, netlist=True) == \
        base + "|netlist"


def test_mixed_input_bits_population_matches_serial(own_pack):
    """Candidates quantizing the ADC lanes at different input_bits get
    per-candidate integer features inside one packed launch; each equals
    its netlist-exact evaluation at P = 1 (`evaluate_spec`), each side
    scoring netlists it packed itself."""
    cfg = PRINTED_MLPS["seeds"]
    n = len(cfg.layer_dims) - 1
    specs = [ModelMin.uniform(n, bits=4, input_bits=4),
             ModelMin.uniform(n, bits=4, input_bits=8)]
    rs = own_pack(BE.evaluate_population, cfg, specs, epochs=8)
    for s, r in zip(specs, rs):
        one = own_pack(MZ.evaluate_spec, cfg, s, epochs=8)
        assert r.accuracy == one.accuracy


def test_packed_launch_fault_propagates(monkeypatch, tmp_path):
    """A fault in the packed-population launch is the engine's, shared by
    every exact candidate: it raises out of `evaluate_population` instead
    of quarantining the generation, and nothing is cached."""
    cfg = PRINTED_MLPS["seeds"]
    n = len(cfg.layer_dims) - 1
    specs = [ModelMin.uniform(n, bits=8),
             ModelMin.uniform(n, bits=3, sparsity=0.3)]

    def boom(*a, **k):
        raise RuntimeError("injected batched-sim fault")

    monkeypatch.setattr(NS, "population_accuracy", boom)
    cache = BE.EvalCache(tmp_path / "evals.json")
    recs = []
    with pytest.raises(RuntimeError, match="injected batched-sim fault"):
        BE.evaluate_population(cfg, specs, epochs=8, cache=cache,
                               quarantine=recs)
    assert recs == [] and len(cache) == 0


@pytest.mark.parametrize("make_error", [
    lambda: ImportError("injected: missing module"),
    lambda: jax.errors.JaxRuntimeError("injected: device lost"),
], ids=["import", "jax_runtime"])
def test_process_fault_in_candidate_propagates(make_error):
    """An ImportError or a JaxRuntimeError raised inside a candidate's
    evaluation is the process's or the device's fault: it propagates
    instead of burning the retry and quarantining the candidate."""
    cfg = PRINTED_MLPS["seeds"]
    n = len(cfg.layer_dims) - 1
    specs = [ModelMin.uniform(n, bits=8)]
    calls = []

    def hook(spec, attempt):
        calls.append(attempt)
        raise make_error()

    prev = BE.set_eval_fault_hook(hook)
    recs = []
    try:
        with pytest.raises(type(make_error())):
            BE.evaluate_population(cfg, specs, epochs=8, quarantine=recs)
    finally:
        BE.set_eval_fault_hook(prev)
    assert calls == [1] and recs == []


def test_pack_cache_reuses_tables(monkeypatch):
    calls = {"n": 0}
    real = NS.pack_netlist

    def counting(net):
        calls["n"] += 1
        return real(net)

    monkeypatch.setattr(NS, "pack_netlist", counting)
    BE._PACK_CACHE.clear()
    key = "unit|pack"
    net = _synth_net((5, 4, 3))
    a = BE._packed_netlist_for(key, net, NS)
    b = BE._packed_netlist_for(key, net, NS)
    assert a is b and calls["n"] == 1
    assert BE._packed_netlist_for(None, net, NS) is not a  # uncached path


def test_pack_cache_lru_cap_and_eviction_counter(monkeypatch):
    from repro.obs import metrics as MT
    monkeypatch.setattr(BE, "_PACK_CACHE_CAP", 3)
    BE._PACK_CACHE.clear()
    ev0 = MT.counter("netlist_sim.pack_evictions").value
    net = _synth_net((5, 4, 3))
    for k in ("a", "b", "c"):
        BE._packed_netlist_for(k, net, NS)
    first_a = BE._PACK_CACHE["a"]
    BE._packed_netlist_for("a", net, NS)          # refresh a's recency
    BE._packed_netlist_for("d", net, NS)          # evicts b (LRU), not a
    assert set(BE._PACK_CACHE) == {"a", "c", "d"}
    assert BE._PACK_CACHE["a"] is first_a
    assert MT.counter("netlist_sim.pack_evictions").value == ev0 + 1
    BE._packed_netlist_for("e", net, NS)          # evicts c
    assert set(BE._PACK_CACHE) == {"a", "d", "e"}
    assert MT.counter("netlist_sim.pack_evictions").value == ev0 + 2
    BE._PACK_CACHE.clear()
