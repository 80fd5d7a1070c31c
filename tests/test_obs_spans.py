"""Program spans (`repro.obs.trace`): ids and parents, the profiler
annotation each span enters, the evaluator's span per layer, and the
executable names the benchmark's device readers match."""
import contextlib
import re

import jax
import jax.numpy as jnp

from repro.configs.printed_mlp import PRINTED_MLPS
from repro.core import batch_eval as BE
from repro.core.compression_spec import ModelMin
from repro.kernels.netlist_sim import ops as NSO
from repro.obs import trace as TR


@contextlib.contextmanager
def _tracing_off():
    prev, TR._tracer = TR._tracer, None
    try:
        yield
    finally:
        TR._tracer = prev


def _spans(path):
    return [r for r in TR.read_trace(path)[0] if r["kind"] == "span"]


# ---------------------------------------------------------------------------
# ids, parents and the profiler annotation
# ---------------------------------------------------------------------------


def test_span_records_carry_id_and_parent(tmp_path):
    p = tmp_path / "t.jsonl"
    with TR.capture(p):
        with TR.span("a"):
            with TR.span("a"):              # same name nested: pops by id
                with TR.span("b"):
                    pass
            with TR.span("c"):
                pass
        with TR.span("d"):
            pass
    by = {(r["name"], r["depth"]): r for r in _spans(p)}
    outer, inner = by[("a", 0)], by[("a", 1)]
    assert outer["parent"] is None
    assert inner["parent"] == outer["id"]
    assert by[("b", 2)]["parent"] == inner["id"]
    assert by[("c", 1)]["parent"] == outer["id"]
    assert by[("d", 0)]["parent"] is None
    ids = [r["id"] for r in _spans(p)]
    assert len(set(ids)) == len(ids) == 5


def test_span_ids_survive_an_exception(tmp_path):
    p = tmp_path / "t.jsonl"
    with TR.capture(p):
        with TR.span("outer"):
            try:
                with TR.span("boom"):
                    raise ValueError("x")
            except ValueError:
                pass
            with TR.span("after"):
                pass
    by = {r["name"]: r for r in _spans(p)}
    assert by["boom"]["error"] == "ValueError"
    assert by["after"]["parent"] == by["outer"]["id"]
    assert by["after"]["depth"] == 1


class _CountingAnnotation:
    made = []

    def __init__(self, name):
        self.name = name
        self.entered = self.exited = 0
        _CountingAnnotation.made.append(self)

    def __enter__(self):
        self.entered += 1
        return self

    def __exit__(self, *exc):
        self.exited += 1


def test_off_path_builds_no_annotation(tmp_path, monkeypatch):
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _CountingAnnotation)
    _CountingAnnotation.made = []
    with _tracing_off():
        with TR.span("off", a=1):
            pass
        assert TR.span("off") is TR._NULL_SPAN
    assert _CountingAnnotation.made == []
    with TR.capture(tmp_path / "t.jsonl"):
        try:
            with TR.span("on", a=1):
                raise KeyError("k")
        except KeyError:
            pass
    (note,) = _CountingAnnotation.made
    assert note.name == "on"                # the name only, no attrs
    assert (note.entered, note.exited) == (1, 1)


def test_spans_land_on_the_profiler_host_plane(tmp_path):
    from jax.profiler import ProfileData
    with TR.capture(tmp_path / "t.jsonl"):
        with jax.profiler.trace(str(tmp_path / "xprof")):
            with TR.span("obs_test.outer"):
                with TR.span("obs_test.inner"):
                    jnp.ones(8).block_until_ready()
    (xplane,) = (tmp_path / "xprof").glob("plugins/profile/*/*.xplane.pb")
    pd = ProfileData.from_file(str(xplane))
    found = {e.name: (e.start_ns, e.end_ns)
             for plane in pd.planes if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name.startswith("obs_test.")}
    outer, inner = found["obs_test.outer"], found["obs_test.inner"]
    assert outer[0] <= inner[0] <= inner[1] <= outer[1]


# ---------------------------------------------------------------------------
# the evaluator's layers
# ---------------------------------------------------------------------------


def test_evaluator_emits_one_span_per_layer(tmp_path):
    cfg = PRINTED_MLPS["seeds"]
    n_layers = len(cfg.layer_dims) - 1
    a = ModelMin.uniform(n_layers, bits=4, input_bits=cfg.input_bits)
    b = ModelMin.uniform(n_layers, bits=3, sparsity=0.2, clusters=4,
                         input_bits=cfg.input_bits)
    p = tmp_path / "t.jsonl"
    with TR.capture(p):
        BE.evaluate_population(cfg, [a, b, a], epochs=2, netlist=True)
        BE.evaluate_population(cfg, [b], epochs=2, netlist=True)
    spans = _spans(p)
    by_id = {r["id"]: r for r in spans}

    def parent(r):
        return by_id[r["parent"]]["name"] if r["parent"] is not None else None

    pops = [r for r in spans if r["name"] == "eval.population"]
    assert len(pops) == 2 and all(r["parent"] is None for r in pops)
    first = pops[0]["id"]
    kids = sorted(r["name"] for r in spans if r["parent"] == first)
    assert kids == ["eval.compile_price", "eval.fetch", "eval.finetune",
                    "eval.prepare"]
    for name, n in (("minimize.compile_bespoke", 3), ("circuit.compile", 3),
                    ("netlist_sim.pack", 2), ("kernels.netlist_sim", 2),
                    ("eval.price", 2), ("eval.compile_price", 2)):
        got = [r for r in spans if r["name"] == name]
        assert len(got) == n, name
        want = ("eval.population" if name == "eval.compile_price"
                else "eval.compile_price")
        assert all(parent(r) == want for r in got), name
    price = [r for r in spans if r["name"] == "eval.compile_price"]
    for cp in price:
        children = [r for r in spans if r["parent"] == cp["id"]]
        assert sum(r["dur"] for r in children) <= cp["dur"] + 1e-6


# ---------------------------------------------------------------------------
# executable names the benchmark's device readers match
# ---------------------------------------------------------------------------

# bench/metrics/finetune_device_ms.py and bench/metrics/netlist_device_ms.py
FINETUNE_PATTERN = r"finetune"
NETLIST_PATTERN = r"run_levels|netlist_sim"


def _module_name(lowered) -> str:
    return re.search(r"module @(\S+)", lowered.as_text()).group(1)


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def test_device_readers_match_the_executable_names():
    f32, i32 = jnp.float32, jnp.int32
    params = {"layers": ({"w": _sds((3, 4), f32), "b": _sds((4,), f32)},
                         {"w": _sds((4, 2), f32), "b": _sds((2,), f32)})}
    masks = (_sds((2, 3, 4), f32), _sds((2, 4, 2), f32))
    finetune = BE._population_finetune.lower(
        params, _sds((2, 2), i32), _sds((2, 2), i32), masks,
        _sds((5, 3), f32), _sds((5,), i32), epochs=1, lr=1e-3)
    wave = _sds((2, 8), i32)
    levels = NSO._run_levels.lower(
        wave, wave, wave, wave, wave, _sds((32,), i32), _sds((2, 3), i32),
        _sds((2, 2), i32), _sds((4, 6), i32))
    ft_name, nl_name = _module_name(finetune), _module_name(levels)
    assert re.search(FINETUNE_PATTERN, ft_name), ft_name
    assert not re.search(NETLIST_PATTERN, ft_name), ft_name
    assert re.search(NETLIST_PATTERN, nl_name), nl_name
    assert not re.search(FINETUNE_PATTERN, nl_name), nl_name


def test_bespoke_launch_is_named_apart_from_the_device_readers():
    """The bespoke compile's population launch is read only through
    `breakdown.device_ops`: neither device reader may count it."""
    f32, i32 = jnp.float32, jnp.int32
    trained = {"layers": ({"w": _sds((2, 3, 4), f32), "b": _sds((2, 4), f32)},
                          {"w": _sds((2, 4, 2), f32),
                           "b": _sds((2, 2), f32)})}
    masks = (_sds((2, 3, 4), f32), _sds((2, 4, 2), f32))
    name = _module_name(BE._population_bespoke.lower(
        trained, _sds((2, 2), i32), _sds((2, 2), i32), masks))
    assert "bespoke" in name, name
    assert not re.search(FINETUNE_PATTERN, name), name
    assert not re.search(NETLIST_PATTERN, name), name
