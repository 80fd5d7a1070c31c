"""Population evaluation: the one evaluation path, for the GA and for
single specs (`minimize.evaluate_spec` is its P = 1 case).

A population's genomes are stacked into padded per-layer arrays (bits,
cluster counts, pruning masks) and QAT-finetuned in a single
`jax.vmap`-over-`lax.scan` jitted call against the shared pretrained
weights (`_population_finetune`); one more launch computes every
candidate's codebooks and quantizer (`_population_bespoke`), and one copy
brings both to the host. Each candidate is then compiled to its bespoke
netlist, all are scored in one `repro.kernels.netlist_sim` launch, and
the population is priced at once through `hw_model.mlp_cost_batch`.

The spec transforms take traced genes:

* quantization: symmetric per-tensor, integer `qmax` built by bit-shift so
  the grid equals `quantization.quantize_int`'s static one;
* clustering: padded Lloyd k-means over `K_MAX` slots with invalid slots
  masked to +inf distance — identical quantile init, identical argmin
  tie-breaking, so valid-slot centroids equal `clustering._kmeans_1d`'s;
* "off" genes (bits=None / clusters=None / sparsity=0) select the identity
  branch through `jnp.where`, multiplying by an all-ones mask.

A persistent on-disk `EvalCache` keyed by (dataset, seed, epochs,
spec.to_json()) makes resumed searches and repeated sweeps free.
"""
from __future__ import annotations

import dataclasses
import functools
import json
import math
import os
import tempfile
import warnings
from collections import OrderedDict
from pathlib import Path
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.printed_mlp import PrintedMLPConfig
from repro.core import hw_model as HW
from repro.core import minimize as MZ
from repro.core.compression_spec import ModelMin
from repro.obs import metrics as MT
from repro.obs import prof as PF
from repro.obs import trace as TR

# Padded k-means slot count: must cover every cluster count the GA can emit
# (core.ga.CLUSTER_CHOICES tops out at 16).
K_MAX = 16


# ---------------------------------------------------------------------------
# evaluation quarantine
# ---------------------------------------------------------------------------

# Worst-case fitness for quarantined specs: finite (inf would poison
# crowding-distance normalization in NSGA-II) but dominated by every real
# candidate, so a quarantined spec can never reach a Pareto front.
QUARANTINE_AREA_MM2 = 1e9
QUARANTINE_POWER_MW = 1e9
QUARANTINE_DELAY_LEVELS = 10 ** 9


@dataclasses.dataclass
class QuarantineRecord:
    """Structured diagnostic for a spec whose evaluation failed.

    A failing candidate (netlist-sim ``OverflowError`` past the 62-bit
    budget, NaN accuracy out of a diverged QAT finetune, any compile
    exception) is retried once and then quarantined with worst-case
    fitness instead of aborting the whole generation — hours of search
    must not die because one genome broke the toolchain.
    """
    spec_json: str
    stage: str              # "compile" | "score"
    error: str              # exception class name
    message: str
    attempts: int


def _worst_case_result(spec: ModelMin) -> MZ.EvalResult:
    return MZ.EvalResult(spec, 0.0, QUARANTINE_AREA_MM2,
                         QUARANTINE_POWER_MW, 0,
                         delay_levels=QUARANTINE_DELAY_LEVELS)


# Fault-injection hook (repro.search.faults): called as hook(spec, attempt)
# at the top of every candidate-evaluation attempt and may raise. None in
# production — the check is a single attribute load.
_EVAL_FAULT_HOOK: Optional[Callable[[ModelMin, int], None]] = None


def set_eval_fault_hook(hook: Optional[Callable[[ModelMin, int], None]]
                        ) -> Optional[Callable]:
    """Install (or clear, with None) the per-candidate fault hook; returns
    the previous hook so callers can restore it."""
    global _EVAL_FAULT_HOOK
    prev, _EVAL_FAULT_HOOK = _EVAL_FAULT_HOOK, hook
    return prev


# ---------------------------------------------------------------------------
# dynamic-spec transforms (traced bits / cluster counts)
# ---------------------------------------------------------------------------


def _padded_kmeans_1d(x: jnp.ndarray, k: jnp.ndarray, k_max: int,
                      iters: int = 25):
    """`clustering._kmeans_1d` with a *traced* cluster count.

    Runs Lloyd iterations over `k_max` centroid slots; slots >= k are held
    at +inf distance so assignments, counts and centroid updates of the
    valid slots reproduce the static-k path exactly (same quantile init,
    same first-index argmin tie-breaking).
    """
    kf = k.astype(jnp.float32)
    slots = jnp.arange(k_max, dtype=jnp.float32)
    valid = slots < kf                                    # (k_max,)
    qs = jnp.clip((slots + 0.5) / kf, 0.0, 1.0)
    cent = jnp.quantile(x, qs)

    def step(cent, _):
        d = jnp.abs(x[:, None] - cent[None, :])           # (N, k_max)
        d = jnp.where(valid[None, :], d, jnp.inf)
        a = jnp.argmin(d, axis=1)
        one = jax.nn.one_hot(a, k_max, dtype=jnp.float32)
        cnt = one.sum(0)
        s = (one * x[:, None]).sum(0)
        new = jnp.where(cnt > 0, s / jnp.maximum(cnt, 1), cent)
        return new, None

    cent, _ = jax.lax.scan(step, cent, None, length=iters)
    d = jnp.abs(x[:, None] - cent[None, :])
    d = jnp.where(valid[None, :], d, jnp.inf)
    a = jnp.argmin(d, axis=1).astype(jnp.int32)
    return cent, a


def _kmeans_rows(w: jnp.ndarray, k: jnp.ndarray, k_max: int):
    """Per-input-row padded k-means of a (d_in, d_out) layer with traced k
    -> (centroids (d_in, k_max), idx (d_in, d_out) int32); k == 0 runs at
    k = 1 for the caller to select away."""
    keff = jnp.maximum(k, 1)
    return jax.vmap(lambda row: _padded_kmeans_1d(row, keff, k_max))(w)


def _cluster_dyn(w: jnp.ndarray, k: jnp.ndarray, k_max: int = K_MAX):
    """Per-input cluster STE with traced k; k == 0 -> identity."""
    wd = jax.lax.stop_gradient(w)
    cent, idx = _kmeans_rows(wd, k, k_max)
    wq = jnp.take_along_axis(cent, idx, axis=1)
    return w + jnp.where(k > 0, wq - wd, 0.0)


def _quant_grid(w: jnp.ndarray, bits: jnp.ndarray):
    """Symmetric per-tensor quantizer with traced bits (>= 2) -> (q, scale),
    q float32 on the integer grid. qmax is built by integer shift so traced
    bits give the exact same grid as `quantization.quantize_int`'s static
    python-float 2**(b-1)-1."""
    qmax = ((jnp.left_shift(jnp.int32(1), bits - 1)) - 1).astype(jnp.float32)
    amax = jnp.maximum(jnp.max(jnp.abs(w)), 1e-8)
    scale = amax / qmax
    return jnp.clip(jnp.round(w / scale), -qmax, qmax), scale


def _quant_dyn(w: jnp.ndarray, bits: jnp.ndarray):
    """Symmetric per-tensor fake-quant STE with traced bits; 0 -> identity
    (the forward of `quantization.fake_quant`)."""
    wd = jax.lax.stop_gradient(w)
    q, scale = _quant_grid(wd, jnp.maximum(bits, 2))
    return w + jnp.where(bits > 0, q * scale - wd, 0.0)


# ---------------------------------------------------------------------------
# population stacking
# ---------------------------------------------------------------------------


def stack_specs(specs: Sequence[ModelMin]) -> Tuple[np.ndarray, np.ndarray]:
    """-> (bits (P, L) int32, clusters (P, L) int32); 0 encodes "off"."""
    bits = np.array([[l.bits or 0 for l in s.layers] for s in specs],
                    np.int32)
    ks = np.array([[l.clusters or 0 for l in s.layers] for s in specs],
                  np.int32)
    return bits, ks


def stack_masks(params0, specs: Sequence[ModelMin]):
    """Magnitude masks from the shared pretrained weights, in both layouts
    the engine needs, from ONE memoized computation per distinct
    (layer, sparsity):

    -> (stacked: per layer (P, d_in, d_out) float32 for the vmapped
        finetune (all-ones when a gene's sparsity is 0),
        serial: per spec, per layer bool mask or None — the exact
        convention `compile_bespoke` / `make_masks` use).
    """
    memo: Dict[Tuple[int, float], Optional[np.ndarray]] = {}

    def mask_for(i, layer, sparsity):
        key = (i, float(sparsity))
        if key not in memo:
            memo[key] = (np.asarray(MZ.P.magnitude_mask(layer["w"],
                                                        sparsity), bool)
                         if sparsity > 0 else None)
        return memo[key]

    layers = params0["layers"]
    serial = [[mask_for(i, layers[i], s.layers[i].sparsity)
               for i in range(len(layers))] for s in specs]
    stacked = [np.stack([np.ones(layers[i]["w"].shape, np.float32)
                         if row[i] is None else row[i].astype(np.float32)
                         for row in serial])
               for i in range(len(layers))]
    return stacked, serial


# ---------------------------------------------------------------------------
# the batched QAT finetune (one jit for the whole population)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("epochs", "lr", "k_max"))
def _population_finetune(params0, bits, ks, masks, x, y, *,
                         epochs: int, lr: float, k_max: int = K_MAX):
    """QAT-finetune P candidates in one vmapped lax.scan train loop.

    params0: shared pretrained pytree; bits/ks: (P, L) int32; masks: tuple
    of L arrays (P, d_in_i, d_out_i) float32. Returns the trained params
    pytree with a leading population axis on every leaf.
    """
    def train_one(bits_row, ks_row, masks_row):
        def t(i, w):
            w = w * masks_row[i]
            w = _cluster_dyn(w, ks_row[i], k_max)
            return _quant_dyn(w, bits_row[i])
        return MZ._train(params0, x, y, epochs=epochs, lr=lr, w_transform=t)

    return jax.vmap(train_one, in_axes=(0, 0, 0))(bits, ks, masks)


# ---------------------------------------------------------------------------
# the bespoke compile's device half (one launch for the whole population)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("k_max",))
def _population_bespoke(trained, bits, ks, masks, *, k_max: int = K_MAX):
    """Device half of `minimize.compile_bespoke` for P candidates in one
    launch, on the trained population where the finetune left it.

    trained: params pytree with a leading population axis; bits/ks: (P, L)
    int32, 0 = off; masks: as `_population_finetune`'s, applied first.
    Returns per layer a dict of (P, ...) arrays: "cb" (d_in, k_max) float32
    and "idx" (d_in, d_out) int32, the per-input k-means the finetune
    trains against (valid slots < k); "q" (d_in, d_out) int32 and "scale"
    float32, the symmetric per-tensor quantizer at bits (0 -> 8, the
    default width). Both are computed for every candidate; the host snap
    (`minimize._snap_bespoke`) reads the one its spec asks for.
    """
    def one(layers, bits_row, ks_row, masks_row):
        out = []
        for i, layer in enumerate(layers):
            w = layer["w"] * masks_row[i]
            cb, idx = _kmeans_rows(w, ks_row[i], k_max)
            q, scale = _quant_grid(
                w, jnp.where(bits_row[i] > 0, bits_row[i], 8))
            out.append({"cb": cb, "idx": idx, "q": q.astype(jnp.int32),
                        "scale": scale})
        return out

    return jax.vmap(one)(trained["layers"], bits, ks, masks)


def _bespoke_k_max(ks: np.ndarray) -> int:
    """k-means slots of the bespoke launch: `K_MAX`, more if a spec asks."""
    return max(K_MAX, int(ks.max(initial=0)))


def bespoke_one(params, spec: ModelMin, masks):
    """`_population_bespoke` at P = 1, `minimize.compile_bespoke`'s device
    half when no launch ran for the candidate: -> its outputs on the host.
    ``masks``: per layer a mask or None, as `minimize.make_masks` gives
    them."""
    bits, ks = stack_specs([spec])
    one = jax.tree_util.tree_map(lambda a: np.asarray(a, np.float32)[None],
                                 params)
    stacked = tuple(
        np.asarray(np.ones(layer["w"].shape[1:]) if m is None else m,
                   np.float32)[None]
        for layer, m in zip(one["layers"], masks))
    out = _population_bespoke(one, bits, ks, stacked,
                              k_max=_bespoke_k_max(ks))
    return jax.tree_util.tree_map(lambda a: a[0], jax.device_get(out))


# ---------------------------------------------------------------------------
# persistent evaluation cache
# ---------------------------------------------------------------------------


def _salvage_entries(text: str) -> Dict[str, Dict]:
    """Best-effort recovery of ``"key": {...}`` pairs from a torn cache
    JSON. Walks the top-level object entry by entry (keys embed escaped
    spec JSON, so this uses the real JSON scanner, not a regex) and stops
    at the first undecodable span — every complete leading entry of a
    truncated file survives."""
    out: Dict[str, Dict] = {}
    decoder = json.JSONDecoder()
    i = text.find("{")
    if i < 0:
        return out
    i += 1
    n = len(text)
    while i < n:
        while i < n and text[i] in ", \t\r\n":
            i += 1
        if i >= n or text[i] != '"':
            break
        try:
            key, i = json.decoder.scanstring(text, i + 1)
            while i < n and text[i] in " \t\r\n":
                i += 1
            if i >= n or text[i] != ":":
                break
            i += 1
            while i < n and text[i] in " \t\r\n":
                i += 1
            val, i = decoder.raw_decode(text, i)
        except (ValueError, IndexError):
            break
        if isinstance(val, dict):
            out[key] = val
    return out


class EvalCache:
    """On-disk cache of spec evaluations with a bounded footprint.

    One JSON file, atomically replaced on flush; keys are
    "dataset|seed=S|epochs=E|spec.to_json()" (suffixed "|netlist" for
    netlist-exact evaluations — a different objective, never mixed with
    analytic entries; approximated specs carry their genes in the spec
    JSON and always live in the netlist keyspace) so resumed searches,
    repeated sweeps and single-spec evaluations all share results.
    ``flush`` re-reads and merges the on-disk file first, so concurrent
    sweep processes sharing a cache file union their entries instead of
    clobbering each other.

    ``max_entries`` caps the cache: every get/put stamps the entry with a
    monotonic access counter, and flush evicts the least-recently-used
    entries beyond the cap — a month of GA sweeps can't grow the file
    without bound. Entries written by pre-cap versions carry no stamp and
    are evicted first. A flush with no new entries and few refreshed
    stamps is a cheap no-op (recency persistence is batched every
    ``TOUCH_FLUSH_EVERY`` hits), so warm fully-cached sweeps don't rewrite
    a multi-MB JSON per generation.
    """

    TOUCH_FLUSH_EVERY = 64

    def __init__(self, path, max_entries: Optional[int] = 100_000):
        self.path = Path(path)
        self.max_entries = max_entries
        self._data: Dict[str, Dict] = self._read()
        self._clock = max((int(e.get("t", 0))
                           for e in self._data.values()), default=0)
        self._dirty = False           # un-persisted put()s
        self._touched = 0             # un-persisted recency stamps

    def _touch(self, entry: Dict) -> Dict:
        self._clock += 1
        entry["t"] = self._clock
        return entry

    def _read(self) -> Dict[str, Dict]:
        if not self.path.exists():
            return {}
        try:
            text = self.path.read_text()
        except OSError as e:
            # unreadable file must not kill a long search — start empty;
            # the next flush atomically replaces it
            warnings.warn(f"EvalCache {self.path} unreadable ({e}); "
                          "starting empty")
            return {}
        try:
            return json.loads(text)
        except json.JSONDecodeError as e:
            # torn/truncated file (crash mid-write on a non-atomic fs,
            # disk-full, fault injection): keep the damaged bytes for the
            # post-mortem and salvage every individually-parseable entry —
            # a multi-day cache must not be discarded over a torn tail
            data = _salvage_entries(text)
            backup = self.path.with_suffix(self.path.suffix + ".corrupt")
            try:
                backup.write_text(text)
            except OSError:
                pass                       # salvage still proceeds
            warnings.warn(f"EvalCache {self.path} corrupt ({e}); salvaged "
                          f"{len(data)} entries, damaged file backed up "
                          f"to {backup}")
            MT.counter("cache.salvages").inc()
            TR.event("cache.salvage", path=str(self.path),
                     salvaged=len(data))
            return data

    @staticmethod
    def key(dataset: str, seed: int, epochs: int, spec: ModelMin,
            netlist: bool = False) -> str:
        base = f"{dataset}|seed={seed}|epochs={epochs}|{spec.to_json()}"
        return base + "|netlist" if netlist else base

    def __len__(self):
        return len(self._data)

    def get(self, dataset: str, seed: int, epochs: int, spec: ModelMin,
            netlist: bool = False) -> Optional[MZ.EvalResult]:
        d = self._data.get(self.key(dataset, seed, epochs, spec, netlist))
        if d is None:
            MT.counter("cache.miss").inc()
            return None
        MT.counter("cache.hit").inc()
        self._touch(d)                  # LRU: a hit keeps the entry young
        self._touched += 1
        return MZ.EvalResult(ModelMin.from_json(d["spec"]), d["accuracy"],
                             d["area_mm2"], d["power_mw"],
                             d["n_multipliers"],
                             delay_levels=d.get("delay_levels"))

    def put(self, dataset: str, seed: int, epochs: int,
            r: MZ.EvalResult, netlist: bool = False) -> None:
        self._data[self.key(dataset, seed, epochs, r.spec, netlist)] = \
            self._touch({
                "spec": r.spec.to_json(), "accuracy": float(r.accuracy),
                "area_mm2": float(r.area_mm2), "power_mw": float(r.power_mw),
                "n_multipliers": int(r.n_multipliers),
                "delay_levels": (None if r.delay_levels is None
                                 else int(r.delay_levels))})
        self._dirty = True

    def flush(self) -> None:
        # nothing new and too few refreshed stamps to be worth a full
        # re-read/merge/rewrite: skip (recency persistence is best-effort)
        if not self._dirty and self._touched < self.TOUCH_FLUSH_EVERY:
            return
        with TR.span("cache.flush", entries=len(self._data)):
            MT.counter("cache.flushes").inc()
            self._flush_locked()

    def _flush_locked(self) -> None:
        self.path.parent.mkdir(parents=True, exist_ok=True)
        # merge concurrent writers under an flock'd sidecar: entries
        # flushed by another process since our last read survive; on a key
        # conflict ours wins (we hold the fresher evaluation of that
        # spec). The lock serializes read-merge-replace so simultaneous
        # flushes cannot interleave and drop each other's entries.
        with open(self.path.with_suffix(self.path.suffix + ".lock"),
                  "w") as lock:
            try:
                import fcntl
                fcntl.flock(lock, fcntl.LOCK_EX)
            except ImportError:       # non-POSIX: merge without the lock
                pass
            disk = self._read()
            if disk:
                disk.update(self._data)
                self._data = disk
            if (self.max_entries is not None
                    and len(self._data) > self.max_entries):
                # LRU-ish eviction: keep the most recently stamped entries
                keep = sorted(self._data.items(),
                              key=lambda kv: int(kv[1].get("t", 0)),
                              reverse=True)[:self.max_entries]
                self._data = dict(keep)
            fd, tmp = tempfile.mkstemp(dir=str(self.path.parent),
                                       prefix=self.path.name + ".")
            try:
                with os.fdopen(fd, "w") as f:
                    json.dump(self._data, f)
                os.replace(tmp, self.path)    # atomic publish
                self._dirty = False
                self._touched = 0
            except BaseException:
                os.unlink(tmp)
                raise


# ---------------------------------------------------------------------------
# population evaluation
# ---------------------------------------------------------------------------

# Per-spec packed node tables for the population netlist-sim engine, keyed
# alongside the EvalCache keyspace (EvalCache.key(..., netlist=True) +
# "|pack"): a netlist is a deterministic function of (dataset, seed,
# epochs, spec) in-process, so a GA revisiting a genome whose EvalResult
# was invalidated (or uncached) never re-lays-out its node tables.
# Process-local, LRU-capped (mirroring EvalCache's max_entries): a
# service-style run cycling through many datasets/specs keeps its working
# set and evicts the least-recently-hit tables — entries are a few dense
# KB each, and `netlist_sim.pack_evictions` counts the churn.
_PACK_CACHE: "OrderedDict[str, object]" = OrderedDict()
_PACK_CACHE_CAP = 2048


def _packed_netlist_for(key: Optional[str], net, NS):
    if key is not None and key in _PACK_CACHE:
        MT.counter("netlist_sim.pack_hits").inc()
        _PACK_CACHE.move_to_end(key)
        return _PACK_CACHE[key]
    packed = NS.pack_netlist(net)
    if key is not None:
        while len(_PACK_CACHE) >= _PACK_CACHE_CAP:
            _PACK_CACHE.popitem(last=False)
            MT.counter("netlist_sim.pack_evictions").inc()
        _PACK_CACHE[key] = packed
    return packed


def _compile_and_price(params_pop, specs, masks_serial, xte, yte, *,
                       bespoke,
                       netlist: bool = True,
                       quarantine: Optional[List[QuarantineRecord]] = None,
                       pack_key: Optional[Callable[[ModelMin], str]] = None
                       ) -> List[MZ.EvalResult]:
    """Host-side bespoke compile per candidate + one vectorized pricing
    call for the whole population. ``params_pop`` and ``bespoke`` (the
    fetched outputs of `_population_bespoke`) are host arrays with a
    leading population axis, so the per-candidate compile is numpy on
    them (`minimize.compile_bespoke(..., prefetched=...)`). Every
    candidate is additionally lowered to its bespoke netlist
    (`repro.circuit`) for the critical-path delay; with ``netlist=True``
    (the default objective) the accuracy is the netlist-exact simulation
    of the printed datapath instead of the float emulation (area/power
    stay on the analytic pricing, which the structural netlist cost is
    tested to reproduce exactly). Every netlist-scored candidate is
    scored in ONE packed-population launch through
    `repro.kernels.netlist_sim` — per-candidate node tables are cached
    under ``pack_key(spec)`` alongside the EvalCache keyspace, so a GA
    revisiting exact genomes repacks nothing.

    Candidates carrying approximation genes (`ModelMin.has_approx`) are
    always netlist-scored: `approx.approximate_and_price` — the host half
    shared with the serial `approx.evaluate_netlist` — runs the passes and
    the approximation-aware structural pricing per candidate (the analytic
    model cannot see truncated circuits), and the *approximated* netlist
    joins the same packed launch as the exact ones for its accuracy.

    Per-candidate fault isolation: a candidate whose compile/score raises
    (or whose accuracy comes back NaN) is retried once and then quarantined
    with worst-case fitness and a :class:`QuarantineRecord` appended to
    ``quarantine`` — the rest of the population prices and returns
    normally. Retrying matters for transient faults (torn files, flaky
    workers); deterministic failures burn both attempts and quarantine.
    ``ImportError`` and ``jax.errors.JaxRuntimeError`` are the process's or
    the device's faults, not the candidate's, and propagate; so does any
    error of the packed-population launch, which every netlist-scored
    candidate shares.
    """
    from repro import approx as AX               # lazy: approx imports us
    from repro import circuit as CIRC            # lazy: circuit imports us
    from repro.kernels import netlist_sim as NS  # lazy: imports circuit

    full: Dict[int, MZ.EvalResult] = {}   # approximated or quarantined
    compiled: Dict[int, MZ.CompiledMLP] = {}   # exact: analytic pricing
    approxed: Dict[int, "AX.PricedApprox"] = {}
    nets: Dict[int, Tuple[object, MZ.CompiledMLP]] = {}  # one launch, below
    accs: Dict[int, float] = {}
    delays: Dict[int, int] = {}

    for p, spec in enumerate(specs):
        err: Optional[Exception] = None
        stage = "compile"
        for attempt in (1, 2):
            try:
                if _EVAL_FAULT_HOOK is not None:
                    _EVAL_FAULT_HOOK(spec, attempt)
                stage = "compile"
                params_p, bespoke_p = jax.tree_util.tree_map(
                    lambda a, p=p: a[p], (params_pop, bespoke))
                c = MZ.compile_bespoke(params_p, spec, masks_serial[p],
                                       prefetched=bespoke_p)
                net = CIRC.compile_netlist(c)
                stage = "score"
                if spec.has_approx:
                    # passes and structural pricing here, accuracy from
                    # the shared launch below
                    approxed[p] = AX.approximate_and_price(net, spec)
                    nets[p] = (approxed[p].net, c)
                elif netlist:
                    # accuracy deferred: every netlist-scored candidate
                    # joins ONE packed-population simulation after this
                    # loop (an integer argmax cannot come back NaN)
                    compiled[p] = c
                    nets[p] = (net, c)
                    delays[p] = net.critical_path_levels()
                else:
                    acc = MZ.compiled_accuracy(c, xte, yte)
                    if math.isnan(float(acc)):
                        raise FloatingPointError(
                            "NaN accuracy out of compiled forward "
                            "(diverged QAT finetune?)")
                    compiled[p] = c
                    accs[p] = float(acc)
                    delays[p] = net.critical_path_levels()
                err = None
                break
            except (ImportError, jax.errors.JaxRuntimeError):
                raise
            except Exception as e:
                err = e
        if err is not None:
            rec = QuarantineRecord(spec.to_json(), stage,
                                   type(err).__name__, str(err), attempts=2)
            MT.counter(f"eval.quarantine.{stage}").inc()
            TR.event("eval.quarantine", stage=stage, error=rec.error,
                     message=rec.message, spec=rec.spec_json)
            if quarantine is not None:
                quarantine.append(rec)
            else:
                warnings.warn(f"spec quarantined ({rec.stage}: {rec.error}: "
                              f"{rec.message}); worst-case fitness assigned")
            full[p] = _worst_case_result(spec)

    # one packed-population launch scores every deferred candidate, exact
    # and approximated; a fault here is the engine's, so it propagates
    if nets:
        todo_p = sorted(nets)
        with TR.span("netlist_sim.pack", n=len(todo_p), approx=len(approxed)):
            # approximated netlists are packed afresh, as
            # `approx.evaluate_netlist` packs them, and stay out of the
            # cache: their accuracy is never read off tables packed from
            # an earlier finetune
            packs = [_packed_netlist_for(
                pack_key(specs[p]) if pack_key and p in compiled else None,
                nets[p][0], NS) for p in todo_p]
            xq = np.stack([np.asarray(
                MZ.quantize_inputs(nets[p][1], xte), np.int64)
                for p in todo_p])
            pop = NS.pack_population(packs)
        pop_acc = NS.population_accuracy(pop, xq, yte)
        for j, p in enumerate(todo_p):
            accs[p] = float(pop_acc[j])
        MT.counter("eval.approx_packed").inc(len(approxed))
    for p, a in approxed.items():
        full[p] = a.result(specs[p], accs[p])

    with TR.span("eval.price", n=len(compiled)):
        return _price(specs, compiled, full, accs, delays)


def _price(specs, compiled: Dict[int, MZ.CompiledMLP],
           full: Dict[int, MZ.EvalResult], accs: Dict[int, float],
           delays: Dict[int, int]) -> List[MZ.EvalResult]:
    """Stack per-layer integer weights / codebooks and price the whole
    population in one hw_model call (codebooks padded to the layer's max
    k). Only cleanly-compiled exact candidates take part; approx-scored
    and quarantined ones already carry their full EvalResult."""
    ok = sorted(compiled)
    cost = None
    if ok:
        comp = [compiled[p] for p in ok]
        L = len(comp[0].q_layers)
        q_layers, w_bits, clusters = [], [], []
        for i in range(L):
            q_layers.append(np.stack([c.q_layers[i] for c in comp]))
            w_bits.append(np.array([c.w_bits[i] for c in comp], np.int64))
            has = np.array([c.clusters[i] is not None for c in comp])
            if has.any():
                kmax = max(c.clusters[i][1].shape[1]
                           for c in comp if c.clusters[i] is not None)
                d_in, d_out = comp[0].q_layers[i].shape
                idx = np.zeros((len(comp), d_in, d_out), np.int64)
                cb = np.zeros((len(comp), d_in, kmax), np.int64)
                for p, c in enumerate(comp):
                    if c.clusters[i] is not None:
                        ci, cc = c.clusters[i]
                        idx[p] = ci
                        cb[p, :, :cc.shape[1]] = cc
                clusters.append((idx, cb, has))
            else:
                clusters.append(None)
        in_bits = np.array([c.input_bits for c in comp], np.int64)
        cost = HW.mlp_cost_batch(q_layers, w_bits=w_bits, in_bits=in_bits,
                                 clusters=clusters)

    pos = {p: j for j, p in enumerate(ok)}
    return [full[p] if p in full
            else MZ.EvalResult(spec, accs[p],
                               float(cost["area_mm2"][pos[p]]),
                               float(cost["power_mw"][pos[p]]),
                               int(cost["n_multipliers"][pos[p]]),
                               delay_levels=delays[p])
            for p, spec in enumerate(specs)]


def _train_population(cfg: PrintedMLPConfig, specs: List[ModelMin], *,
                      epochs: int, seed: int):
    """Stage, QAT-finetune and bespoke-launch ``specs`` (distinct) on the
    device, then fetch both in one copy.

    -> (trained, bespoke, masks, (xte, yte)): the trained params and the
    `_population_bespoke` outputs as host arrays with a leading axis of
    ``len(specs)``, each spec's per-layer masks (`stack_masks`' serial
    layout) and the test split. Counts ``eval.specs_evaluated`` once per
    spec, as `minimize.compile_bespoke` counts ``eval.bespoke_prefetched``
    once per compile from these outputs. Spans: ``eval.prepare``
    (pretrain memo, `stack_specs`, `stack_masks`, bucket padding,
    uploads), ``eval.finetune`` (under tracing) and ``eval.fetch``.
    """
    n_real = len(specs)
    MT.counter("eval.specs_evaluated").inc(n_real)
    with TR.span("eval.prepare"):
        # pad to a power-of-two bucket by repeating the last spec: the
        # jit specializes on the population axis, and a GA's uncached
        # count differs almost every generation — bucketing keeps one
        # compiled executable per bucket instead of a retrace per
        # generation
        bucket = 1 << (n_real - 1).bit_length()
        padded = specs + [specs[-1]] * (bucket - n_real)
        params0, (xtr, ytr, xte, yte) = MZ.pretrain(cfg, seed=seed)
        bits, ks = stack_specs(padded)
        stacked, masks_serial = stack_masks(params0, padded)
        masks = tuple(jnp.asarray(m) for m in stacked)
        # population-bucket padding accounting (same convention as
        # netlist_sim's lane padding): real specs vs padded bucket slots
        MT.counter("eval.pad.specs_real").inc(n_real)
        MT.counter("eval.pad.specs_total").inc(bucket)
        bits_j, ks_j = jnp.asarray(bits), jnp.asarray(ks)
        xtr_j, ytr_j = jnp.asarray(xtr), jnp.asarray(ytr)
        args = (params0, bits_j, ks_j, masks, xtr_j, ytr_j)
        kw = dict(epochs=epochs, lr=2e-3)

    # the dispatch wrapper times DISPATCH of the population jit (never
    # runs inside traced code); the first call per (dataset, bucket,
    # epochs) pays XLA compilation, is tagged `first` so reports split
    # compile_ms out, and has its cost/memory analysis captured into
    # the executable registry
    if not TR.active():
        trained = _population_finetune(*args, **kw)
    else:
        TR.event("eval.padding", dataset=cfg.name, specs_real=n_real,
                 specs_total=bucket)
        key = ("finetune", cfg.name, bucket, epochs, tuple(cfg.layer_dims))
        with PF.dispatch("eval.finetune", key,
                         lower=lambda: _population_finetune.lower(
                             *args, **kw),
                         dataset=cfg.name, bucket=bucket, n=n_real):
            trained = _population_finetune(*args, **kw)
            jax.block_until_ready(trained)
    with TR.span("eval.fetch", n=n_real):
        # the bespoke compile's device half for the whole population,
        # queued behind the finetune before anything syncs, then ONE
        # device-to-host copy of both; the bucket padding is cut on the
        # host
        bespoke = _population_bespoke(trained, bits_j, ks_j, masks,
                                      k_max=_bespoke_k_max(ks))
        trained, bespoke = jax.tree_util.tree_map(
            lambda a: a[:n_real], jax.device_get((trained, bespoke)))
    return trained, bespoke, masks_serial[:n_real], (xte, yte)


def compile_one(cfg: PrintedMLPConfig, spec: ModelMin, *, epochs: int = 150,
                seed: int = 0) -> MZ.CompiledMLP:
    """The evaluator's compile at P = 1: `_train_population` on ``spec``,
    then `minimize.compile_bespoke` on its fetched outputs — the
    `CompiledMLP` `evaluate_population([spec])` scores."""
    trained, bespoke, masks, _ = _train_population(cfg, [spec],
                                                   epochs=epochs, seed=seed)
    one = jax.tree_util.tree_map(lambda a: a[0], (trained, bespoke))
    return MZ.compile_bespoke(one[0], spec, masks[0], prefetched=one[1])


def evaluate_population(cfg: PrintedMLPConfig, specs: Sequence[ModelMin], *,
                        epochs: int = 150, seed: int = 0,
                        cache: Optional[EvalCache] = None,
                        netlist: bool = True,
                        quarantine: Optional[List[QuarantineRecord]] = None
                        ) -> List[MZ.EvalResult]:
    """Evaluate a population of specs with ONE vmapped QAT finetune + ONE
    vectorized pricing pass. Order-preserving; duplicates and cache hits
    are evaluated once; `minimize.evaluate_spec` is the call at P = 1.

    The accuracy objective defaults to the bit-exact simulation of each
    candidate's compiled netlist (`repro.circuit`) — the printed datapath
    itself, integer biases and all — scored for the whole population in
    one `repro.kernels.netlist_sim` launch and cached under a separate key
    space (old analytic cache entries keep their exact byte keys).
    ``netlist=False`` opts back out to the float emulation
    (`minimize.compiled_accuracy`). Specs with approximation genes are
    always scored on their simulated approximated netlist, in the same
    launch, and priced structurally, whatever ``netlist`` says; they live
    in the netlist keyspace (their genes are part of the spec JSON, so they
    can never collide with an exact entry).

    A candidate whose compile/score fails is retried once, then quarantined
    with worst-case fitness (never cached, so a fixed toolchain re-evaluates
    it) and a :class:`QuarantineRecord` appended to ``quarantine`` — one
    poisoned genome cannot abort the generation. Faults of the process, the
    device or the shared packed-population launch raise out of this call.
    """
    specs = list(specs)
    with TR.span("eval.population", dataset=cfg.name, n=len(specs)):
        results = _evaluate_unique(cfg, specs, epochs=epochs, seed=seed,
                                   cache=cache, netlist=netlist,
                                   quarantine=quarantine)
    return [results[s.to_json()] for s in specs]


def _evaluate_unique(cfg: PrintedMLPConfig, specs: List[ModelMin], *,
                     epochs: int, seed: int, cache: Optional[EvalCache],
                     netlist: bool,
                     quarantine: Optional[List[QuarantineRecord]]
                     ) -> Dict[str, MZ.EvalResult]:
    """`evaluate_population`'s body: spec json -> result, each distinct
    spec once: the cache lookup, then `_train_population` on the specs
    left to evaluate and ``eval.compile_price`` on what it fetched."""
    from repro.verify.diagnostics import verify_enabled
    if specs and verify_enabled():
        # static spec lint before any costly QAT: gene-range/arch
        # legality + serialize->parse->serialize byte-stability (a
        # non-round-tripping spec would fracture the cache keyspace)
        from repro.verify.spec import check_specs
        check_specs(specs, cfg)
    results: Dict[str, MZ.EvalResult] = {}
    todo: List[ModelMin] = []
    queued = set()
    n_hits = 0
    for s in specs:
        k = s.to_json()
        if k in results or k in queued:
            continue
        hit = (cache.get(cfg.name, seed, epochs, s,
                         netlist=netlist or s.has_approx)
               if cache else None)
        n_hits += hit is not None
        if hit is not None and hit.delay_levels is not None:
            # entries from caches predating the circuit compiler carry
            # no delay — fall through and re-evaluate so they upgrade
            # in place
            results[k] = hit
        else:
            todo.append(s)
            queued.add(k)

    MT.counter("eval.specs_requested").inc(len(specs))
    MT.counter("eval.specs_cached").inc(n_hits)
    TR.event("eval.batch", dataset=cfg.name, requested=len(specs),
             hits=n_hits, evaluated=len(todo))

    if todo:
        trained, bespoke, masks, (xte, yte) = _train_population(
            cfg, todo, epochs=epochs, seed=seed)
        recs: List[QuarantineRecord] = []

        def pack_key(s: ModelMin) -> str:
            return EvalCache.key(cfg.name, seed, epochs, s,
                                 netlist=True) + "|pack"

        with TR.span("eval.compile_price", dataset=cfg.name, n=len(todo)):
            priced = _compile_and_price(trained, todo, masks, xte, yte,
                                        bespoke=bespoke, netlist=netlist,
                                        quarantine=recs, pack_key=pack_key)
        for r in priced:
            results[r.spec.to_json()] = r
            if cache is not None and \
                    all(q.spec_json != r.spec.to_json() for q in recs):
                cache.put(cfg.name, seed, epochs, r,
                          netlist=netlist or r.spec.has_approx)
        if recs:
            if quarantine is not None:
                quarantine.extend(recs)
            else:
                warnings.warn(f"{len(recs)} spec(s) quarantined with "
                              "worst-case fitness; pass quarantine=[] to "
                              "collect the structured records")

    # flush on hits too: a get() refreshes the entry's LRU stamp, and a
    # long fully-cached resume must persist that recency or a capped
    # writer would evict exactly the entries this sweep is actively
    # reusing (the cache itself batches recency-only writes, so a warm
    # generation is not a multi-MB rewrite)
    if cache is not None and (todo or n_hits):
        cache.flush()
    return results


def make_batch_evaluator(cfg: PrintedMLPConfig, *, epochs: int = 150,
                         seed: int = 0,
                         cache: Optional[EvalCache] = None,
                         netlist: bool = True,
                         include_delay: bool = False,
                         record: Optional[Dict[str, MZ.EvalResult]] = None,
                         quarantine: Optional[List[QuarantineRecord]]
                         = None):
    """GA adapter: List[ModelMin] -> List[(1 - accuracy, area_mm2[,
    delay_levels])]. Plug into `run_nsga2(..., batch_evaluate=...)`.

    The accuracy objective is netlist-exact by default (the simulated
    printed datapath, batched through `repro.kernels.netlist_sim`);
    ``netlist=False`` opts out to the analytic float emulation.
    ``include_delay=True`` adds the compiled
    circuit's critical path as a third minimized objective. ``record``, if
    given, collects every EvalResult by spec json — callers (fig2, the
    example) read Pareto-front delay out of it without re-evaluating.
    Specs carrying approximation genes are approximated and priced
    structurally per candidate by `evaluate_population`, and their
    approximated netlists simulated in the population's one launch,
    whatever ``netlist`` says. ``quarantine``, if given, collects
    the `QuarantineRecord`s of failing specs — share the list with
    `run_nsga2(quarantine=...)` / the island runtime so quarantined specs
    surface on the final result.
    """
    def batch_evaluate(specs: Sequence[ModelMin]):
        rs = evaluate_population(cfg, specs, epochs=epochs, seed=seed,
                                 cache=cache, netlist=netlist,
                                 quarantine=quarantine)
        if record is not None:
            record.update((r.spec.to_json(), r) for r in rs)
        if include_delay:
            return [(1.0 - r.accuracy, r.area_mm2, float(r.delay_levels))
                    for r in rs]
        return [(1.0 - r.accuracy, r.area_mm2) for r in rs]
    return batch_evaluate
