"""Population netlist simulation: one launch for P candidates x B samples.

The whole population runs through one shape-stable executable, where a
per-netlist jit (`circuit.simulate.Simulator`) would trace and compile
once per candidate. Shapes are bucketed to powers of two so GA generations
reuse executables instead of retracing.

The engine, ``levels``: a host-built global wave schedule over the
concatenated node tables, executed as ONE ``lax.scan`` over fixed
(window,)-wide waves with branchless opcode dispatch. Each global
topological level is chunked into ceil(count/window) waves; every wave of
level l-1 precedes every wave of level l, so intra-wave independence is
inherited from the level structure. Padding lanes carry ``op = NOP`` and
scatter to a dummy slot.

Lane width is the verifier's per-node bound maximized over the population:
int32 when every word fits 32 bits, else int64 under a local
``jax.enable_x64(True)`` scope (`repro.verify.netlist.fits_int32`
semantics). The engine is bit-exact against `circuit.simulate.simulate`
and the NumPy oracle in `ref.py` (`simulate_population_ref`), tested on
all four datasets.
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.circuit import ir
from repro.kernels.netlist_sim.pack import (NOP, PackedPopulation,
                                            pack_netlist, pack_population)
from repro.kernels.netlist_sim.ref import _normalize_x
from repro.obs import metrics as MT
from repro.obs import prof as PF
from repro.obs import trace as TR

_CONST = int(ir.Op.CONST)
_SHL = int(ir.Op.SHL)
_ADD = int(ir.Op.ADD)
_SUB = int(ir.Op.SUB)
_NEG = int(ir.Op.NEG)
_RELU = int(ir.Op.RELU)
_ARGMAX = int(ir.Op.ARGMAX)


def _bucket(n: int) -> int:
    """Next power of two >= n (>= 1): the jit specializes on shapes, and
    bucketing keeps one executable per bucket across GA generations."""
    return 1 << (max(int(n), 1) - 1).bit_length()


@dataclasses.dataclass(frozen=True)
class _Schedule:
    """Host-derived global wave schedule (all arrays numpy, jnp-ready)."""
    OP: np.ndarray        # (n_waves, W) int32, NOP on padding lanes
    AI: np.ndarray        # (n_waves, W) int32 global operand positions
    BI: np.ndarray        # (n_waves, W) int32
    SH: np.ndarray        # (n_waves, W) int32 immediates (0 elsewhere)
    OUT: np.ndarray       # (n_waves, W) int32 global out positions
    vals0: np.ndarray     # (N_buf,) int64 CONST-seeded initial buffer
    inp_cols: np.ndarray  # (P, n_in) int32 global input positions
    am_cols: np.ndarray   # (P, C) int32 global comparator-operand positions
    n_waves: int          # real (pre-bucket) wave count


def _global_schedule(pop: PackedPopulation, window: int) -> _Schedule:
    """Concatenate the population's tables into one flat position space
    (candidate p's slot s lives at ``off[p] + s``) and chunk each global
    level into fixed-width waves. All vectorized numpy — no per-node
    python loop."""
    P, N = pop.op.shape
    n = pop.n_nodes.astype(np.int64)
    off = np.zeros(P, np.int64)
    off[1:] = np.cumsum(n)[:-1]
    total = int(n.sum())
    slot = np.arange(N, dtype=np.int64)
    valid = slot[None, :] < n[:, None]                    # (P, N)
    gpos = slot[None, :] + off[:, None]                   # (P, N)
    lvls = np.zeros((P, N), np.int64)
    for p in range(P):
        ptr = pop.level_ptr[p].astype(np.int64)
        lvls[p, :n[p]] = np.repeat(np.arange(ptr.size - 1), np.diff(ptr))

    comp = valid & (pop.op >= _SHL) & (pop.op != _ARGMAX)
    op_c = pop.op[comp].astype(np.int64)
    a_c = (pop.arg_a + off[:, None])[comp]
    b_c = (pop.arg_b + off[:, None])[comp]
    sh_c = pop.shift[comp].astype(np.int64)
    out_c = gpos[comp]
    lv_c = lvls[comp]

    ordr = np.argsort(lv_c, kind="stable")
    op_s, a_s, b_s = op_c[ordr], a_c[ordr], b_c[ordr]
    sh_s, out_s, lv_s = sh_c[ordr], out_c[ordr], lv_c[ordr]
    M = op_s.size

    counts = np.bincount(lv_s) if M else np.zeros(1, np.int64)
    wins = -(-counts // window)                           # ceil per level
    wstart = np.concatenate([[0], np.cumsum(wins)])
    lfirst = np.concatenate([[0], np.cumsum(counts)])
    rank = np.arange(M) - lfirst[lv_s]
    row = wstart[lv_s] + rank // window
    col = rank % window

    nw = _bucket(int(wstart[-1]))
    n_buf = _bucket(total + 1)                            # +1: dummy slot
    dummy = n_buf - 1
    OP = np.full((nw, window), NOP, np.int32)
    AI = np.zeros((nw, window), np.int32)
    BI = np.zeros((nw, window), np.int32)
    SH = np.zeros((nw, window), np.int32)
    OUT = np.full((nw, window), dummy, np.int32)
    OP[row, col] = op_s
    AI[row, col] = a_s
    BI[row, col] = b_s
    SH[row, col] = sh_s
    OUT[row, col] = out_s

    vals0 = np.zeros(n_buf, np.int64)
    cmask = valid & (pop.op == _CONST)
    vals0[gpos[cmask]] = pop.val[cmask]
    return _Schedule(
        OP=OP, AI=AI, BI=BI, SH=SH, OUT=OUT, vals0=vals0,
        inp_cols=(pop.input_pos + off[:, None]).astype(np.int32),
        am_cols=(pop.argmax_pos + off[:, None]).astype(np.int32),
        n_waves=int(wstart[-1]))


@jax.jit
def _run_levels(OP, AI, BI, SH, OUT, vals0, inp_cols, am_cols, x):
    """x: (B, P*n_in) already in the lane dtype. -> (B, P, C) comparator
    operands. One scan over waves; every lane dispatches branchlessly on
    its opcode (padding lanes fall through to the TRUNC arm with shift 0
    and scatter to the dummy slot)."""
    B = x.shape[0]
    vals = jnp.tile(vals0[None, :], (B, 1))
    vals = vals.at[:, inp_cols.reshape(-1)].set(x)

    def step(vals, wave):
        o, ai, bi, sh, out = wave
        a = jnp.take(vals, ai, axis=1)
        b = jnp.take(vals, bi, axis=1)
        r = jnp.where(o == _SHL, jnp.left_shift(a, sh),
            jnp.where(o == _ADD, a + b,
            jnp.where(o == _SUB, a - b,
            jnp.where(o == _NEG, -a,
            jnp.where(o == _RELU, jnp.maximum(a, 0),
                      # TRUNC (and NOP padding, with sh = 0)
                      jnp.left_shift(jnp.right_shift(a, sh), sh))))))
        return vals.at[:, out].set(r), None

    vals, _ = jax.lax.scan(step, vals, (OP, AI, BI, SH, OUT))
    return jnp.take(vals, am_cols, axis=1)                # (B, P, C)


def _pad_candidates(pop: PackedPopulation, x: np.ndarray, p_pad: int):
    """Repeat candidate 0 up to the population bucket so the executable
    specializes on bucketed shapes only."""
    reps = p_pad - pop.n_candidates
    if reps <= 0:
        return pop, x
    tile2 = lambda a: np.concatenate([a, np.repeat(a[:1], reps, 0)])  # noqa: E731
    pop2 = PackedPopulation(
        op=tile2(pop.op), arg_a=tile2(pop.arg_a), arg_b=tile2(pop.arg_b),
        shift=tile2(pop.shift), val=tile2(pop.val),
        orig_id=tile2(pop.orig_id), level_ptr=tile2(pop.level_ptr),
        input_pos=tile2(pop.input_pos), argmax_pos=tile2(pop.argmax_pos),
        n_nodes=tile2(pop.n_nodes), n_levels=tile2(pop.n_levels),
        max_width=pop.max_width)
    return pop2, tile2(x)


def _real_ops(pop: PackedPopulation) -> int:
    """Computational (wave-scheduled) ops over the real candidates."""
    n = pop.n_nodes.astype(np.int64)
    valid = np.arange(pop.op.shape[1])[None, :] < n[:, None]
    return int((valid & (pop.op >= _SHL) & (pop.op != _ARGMAX)).sum())


def _run_engine(pop: PackedPopulation, x: np.ndarray, window: int,
                block_b: int) -> Tuple[np.ndarray, Dict]:
    """-> (amx, stats): amx (P, B, C) int64 for the real (unpadded)
    candidates; stats the launch's padding/executable accounting —
    ``key`` is the *exact* static-shape specialization tuple of the jit
    this launch dispatches (the executable-observatory identity), and
    the ``*_used``/``*_total`` pairs measure real work vs padded work
    (NOP wave lanes, repeated candidates, repeated batch rows)."""
    P, B = x.shape[0], x.shape[1]
    fits32 = pop.max_width <= 32
    scope = contextlib.nullcontext() if fits32 else jax.enable_x64(True)
    dtype = jnp.int32 if fits32 else jnp.int64
    lane = "int32" if fits32 else "int64"

    p_pad = _bucket(P)
    ppad, xpad = _pad_candidates(pop, x, p_pad)
    sched = _global_schedule(ppad, window)
    bt = min(_bucket(B), block_b)
    n_tiles = -(-B // bt)
    nw, W = sched.OP.shape
    n_buf = sched.vals0.size
    n_in, C = sched.inp_cols.shape[1], sched.am_cols.shape[1]
    stats = {
        "key": ("netlist_levels", nw, W, n_buf, p_pad, n_in, C, bt, lane),
        "cand_real": P, "cand_total": p_pad,
        # wave lanes actually carrying an op (incl. the repeated padding
        # candidates) vs the bucketed wave grid
        "lanes_used": int((sched.OP != NOP).sum()),
        "lanes_total": nw * W,
        "ops_real": _real_ops(pop),
        "rows_real": B, "rows_total": n_tiles * bt,
        "tiles": n_tiles,
    }

    def _lower():
        tile = xc[0:bt]
        pad = bt - tile.shape[0]
        if pad:
            tile = np.concatenate([tile, tile[-1:].repeat(pad, 0)])
        with (contextlib.nullcontext() if fits32
              else jax.enable_x64(True)):
            return _run_levels.lower(*args, vals0, inp_cols, am_cols,
                                     jnp.asarray(tile.astype(dtype)))

    outs = []
    ctx = (PF.dispatch("kernels.netlist_sim.levels", stats["key"],
                       lower=_lower, p=P, b=B, tiles=n_tiles)
           if TR.active() else contextlib.nullcontext())
    with ctx, scope:
        args = [jnp.asarray(a) for a in
                (sched.OP, sched.AI, sched.BI, sched.SH, sched.OUT)]
        vals0 = jnp.asarray(sched.vals0.astype(dtype))
        inp_cols = jnp.asarray(sched.inp_cols)
        am_cols = jnp.asarray(sched.am_cols)
        # (P, B, n_in) -> (B, P*n_in) columns in global-position order
        xc = np.ascontiguousarray(xpad.transpose(1, 0, 2).reshape(B, -1))
        for b0 in range(0, B, bt):
            tile = xc[b0:b0 + bt]
            pad = bt - tile.shape[0]
            if pad:
                tile = np.concatenate([tile, tile[-1:].repeat(pad, 0)])
            amx = _run_levels(*args, vals0, inp_cols, am_cols,
                              jnp.asarray(tile.astype(dtype)))
            outs.append(np.asarray(amx[:bt - pad], np.int64))
    amx = np.concatenate(outs).transpose(1, 0, 2)         # (P_pad, B, C)
    return amx[:P], stats


def simulate_population(pop: PackedPopulation, x: np.ndarray, *,
                        window: int = 256, block_b: int = 2048
                        ) -> Dict[str, np.ndarray]:
    """Simulate P packed candidates over a batch in one launch.

    x: (B, n_in) shared inputs or (P, B, n_in) per-candidate (candidates
    quantizing the ADC lanes at different ``input_bits`` need their own
    integer features). ``window`` is the wave width, ``block_b`` the
    largest input tile.

    -> {"amx": (P, B, C) int64 comparator operands,
        "argmax": (P, B) int64 class decisions} — bit-exact vs
    `circuit.simulate.simulate` per candidate.
    """
    x = np.asarray(_normalize_x(pop, x))
    P, B = x.shape[0], x.shape[1]
    MT.counter("netlist_sim.launches").inc()
    MT.counter("netlist_sim.candidates").inc(P)
    if not TR.active():
        amx, stats = _run_engine(pop, x, window, block_b)
    else:
        with TR.span("kernels.netlist_sim", p=P, b=B,
                     slots=int(pop.n_nodes.sum())):
            amx, stats = _run_engine(pop, x, window, block_b)
    _account_padding(stats)
    return {"amx": amx, "argmax": np.argmax(amx, axis=-1).astype(np.int64)}


def _account_padding(stats: Dict) -> None:
    """Always-on packing-efficiency accounting for one launch. Counters
    hold exact lane/row totals (deterministic functions of the evaluated
    populations, so they keep the checkpoint bit-identity contract); the
    full per-launch stats ride the trace as a ``netlist_sim.padding``
    event when tracing."""
    MT.counter("netlist_sim.pad.lanes_used").inc(stats["lanes_used"])
    MT.counter("netlist_sim.pad.lanes_total").inc(stats["lanes_total"])
    MT.counter("netlist_sim.pad.rows_real").inc(stats["rows_real"])
    MT.counter("netlist_sim.pad.rows_total").inc(stats["rows_total"])
    MT.counter("netlist_sim.pad.cand_real").inc(stats["cand_real"])
    MT.counter("netlist_sim.pad.cand_total").inc(stats["cand_total"])
    if TR.active():
        TR.event("netlist_sim.padding",
                 **{k: (PF.key_str(v) if k == "key" else v)
                    for k, v in stats.items()})


def population_accuracy(pop: PackedPopulation, x: np.ndarray,
                        y: np.ndarray, **kw) -> np.ndarray:
    """Netlist-exact test accuracy per candidate: -> (P,) float64. ``x``
    must already be ADC-quantized integers (see
    `minimize.quantize_inputs`)."""
    cls = simulate_population(pop, x, **kw)["argmax"]
    return np.mean(cls == np.asarray(y)[None, :], axis=1)


__all__ = ["simulate_population", "population_accuracy", "pack_netlist",
           "pack_population"]
