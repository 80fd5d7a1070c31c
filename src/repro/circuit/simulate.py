"""Bit-exact batched netlist simulation in JAX.

The netlist is static per compiled model, so all scheduling happens once on
the host: nodes are grouped into topological levels and, within each level,
by opcode. The resulting plan is a short list of gather -> elementwise-op ->
scatter steps over one flat value buffer; the evaluator is a single jitted
function, ``vmap``-ed over the input batch. Every intermediate is an exact
machine integer — int32 when the verifier's per-node width bounds say every
datapath word fits a 32-bit lane (`repro.verify.netlist.fits_int32`; the
bound is inclusive at width 32, i.e. exactly the int32 range), int64 (under
a local ``jax.enable_x64(True)`` scope) otherwise — so the simulation
reproduces `minimize.integer_forward` bit-for-bit; there is no float
anywhere in the datapath.

For *population* throughput (the GA's netlist-exact objective) use
`repro.kernels.netlist_sim`: this module rebuilds a jitted executable per
netlist, which is exactly the per-candidate compile cost the packed
population engine exists to amortize. `netlist_accuracy` below already
routes through it.
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.circuit import ir


@dataclasses.dataclass(frozen=True)
class _Step:
    """One level-batched op group: out[i] = op(a[i] [, b[i] | shift[i]])."""
    op: ir.Op
    out: np.ndarray                   # node ids to write
    a: np.ndarray                     # first-arg node ids
    b: np.ndarray                     # second-arg ids (ADD/SUB) or shifts


@dataclasses.dataclass(frozen=True)
class SimPlan:
    n_nodes: int
    const_ids: np.ndarray
    const_vals: np.ndarray
    input_ids: np.ndarray
    steps: Tuple[_Step, ...]
    pre_ids: Tuple[np.ndarray, ...]   # per-layer integer pre-activations
    output_ids: np.ndarray
    # the ARGMAX node's actual operands: equal to output_ids on exact
    # netlists, but approximation passes may interpose comparator-input
    # TRUNC nodes — the decision must be taken over what the printed
    # comparator tree actually sees
    argmax_ids: np.ndarray
    max_width: int


def build_plan(net: ir.Netlist) -> SimPlan:
    """Schedule the netlist: per topological level, per opcode, one step."""
    steps: List[_Step] = []
    consts: List[Tuple[int, int]] = []
    for level in net.levels():
        by_op: Dict[ir.Op, List[int]] = {}
        for nid in level:
            n = net.nodes[nid]
            if n.op == ir.Op.CONST:
                consts.append((nid, n.value))
            elif n.op in (ir.Op.INPUT, ir.Op.ARGMAX):
                continue              # inputs seeded, argmax done at the end
            else:
                by_op.setdefault(n.op, []).append(nid)
        for op, ids in sorted(by_op.items()):
            nodes = [net.nodes[i] for i in ids]
            a = np.array([n.args[0] for n in nodes], np.int32)
            if op in (ir.Op.SHL, ir.Op.TRUNC):
                b = np.array([n.shift for n in nodes], np.int32)
            elif op in (ir.Op.ADD, ir.Op.SUB):
                b = np.array([n.args[1] for n in nodes], np.int32)
            else:                     # NEG / RELU: unary
                b = np.zeros(len(nodes), np.int32)
            steps.append(_Step(op, np.array(ids, np.int32), a, b))
    cid = np.array([c[0] for c in consts], np.int32)
    cval = np.array([c[1] for c in consts], np.int64)
    am = (net.nodes[net.argmax_id].args if net.argmax_id is not None
          else net.output_ids)
    return SimPlan(
        n_nodes=len(net), const_ids=cid, const_vals=cval,
        input_ids=np.array(net.input_ids, np.int32),
        steps=tuple(steps),
        pre_ids=tuple(np.array(p, np.int32) for p in net.layer_pre_ids),
        output_ids=np.array(net.output_ids, np.int32),
        argmax_ids=np.array(am, np.int32),
        max_width=net.max_width)


def _evaluate(plan: SimPlan, x: jnp.ndarray, dtype
              ) -> Tuple[List[jnp.ndarray], jnp.ndarray]:
    """One sample through the plan. x: (n_inputs,) int. Returns (per-layer
    pre-activation vectors, the argmax comparator's operand vector) — the
    dataflow is pure integer throughout."""
    vals = jnp.zeros(plan.n_nodes, dtype)
    vals = vals.at[plan.const_ids].set(plan.const_vals.astype(dtype))
    vals = vals.at[plan.input_ids].set(x.astype(dtype))
    for s in plan.steps:
        a = vals[s.a]
        if s.op == ir.Op.SHL:
            r = jnp.left_shift(a, s.b.astype(dtype))
        elif s.op == ir.Op.TRUNC:
            # arithmetic shift right then left: floor-truncate the low bits
            k = s.b.astype(dtype)
            r = jnp.left_shift(jnp.right_shift(a, k), k)
        elif s.op == ir.Op.ADD:
            r = a + vals[s.b]
        elif s.op == ir.Op.SUB:
            r = a - vals[s.b]
        elif s.op == ir.Op.NEG:
            r = -a
        else:                         # RELU
            r = jnp.maximum(a, 0)
        vals = vals.at[s.out].set(r)
    return [vals[p] for p in plan.pre_ids], vals[plan.argmax_ids]


class Simulator:
    """Compiled batched evaluator for one netlist.

    ``run(x_int)`` -> dict with per-layer integer ``pre`` activations,
    integer ``logits`` and the ``argmax`` class — all exact. The jitted
    executable is built once and reused across calls; int64 netlists are
    traced and executed inside a local x64 scope (the repo default stays
    32-bit everywhere else).
    """

    def __init__(self, net: ir.Netlist):
        # lazy: repro.verify imports repro.circuit for the IR types
        from repro.verify.netlist import fits_int32
        self.plan = build_plan(net)
        # per-node width bounds, inclusive at 32: a width-32 word is
        # exactly the int32 range, and the old whole-net `max_width > 31`
        # check promoted such nets to 64-bit lanes they never needed
        self._x64 = not fits_int32(net)
        dtype = jnp.int64 if self._x64 else jnp.int32

        def batch(x):                 # x: (B, n_inputs)
            pres, amx = jax.vmap(
                lambda row: _evaluate(self.plan, row, dtype))(x)
            # decide over what the comparator tree actually sees (its
            # inputs may be truncated by the approximation passes)
            return pres, jnp.argmax(amx, axis=-1)

        with self._scope():
            self._fn = jax.jit(batch)

    def _scope(self):
        return jax.enable_x64(True) if self._x64 else contextlib.nullcontext()

    def run(self, x_int: np.ndarray) -> Dict[str, np.ndarray]:
        x = np.asarray(x_int)
        squeeze = x.ndim == 1
        if squeeze:
            x = x[None]
        with self._scope():
            pres, cls = self._fn(jnp.asarray(x))
            pres = [np.asarray(p, np.int64) for p in pres]
            cls = np.asarray(cls)
        if squeeze:
            pres, cls = [p[0] for p in pres], cls[0]
        return {"pre": pres, "logits": pres[-1], "argmax": cls}


def simulate(net: ir.Netlist, x_int: np.ndarray) -> Dict[str, np.ndarray]:
    """One-shot helper (builds a fresh Simulator; reuse Simulator for
    repeated batches)."""
    return Simulator(net).run(x_int)


def netlist_accuracy(net: ir.Netlist, c, x: np.ndarray,
                     y: np.ndarray) -> float:
    """Netlist-exact test accuracy: ADC-quantize features with the QAT
    compile's rounding, evaluate the printed datapath, compare argmax.

    A P = 1 launch of the population engine (`repro.kernels.netlist_sim`),
    whose executables specialize on bucketed shapes shared across a
    dataset's candidates, so repeated one-netlist scoring (the approx
    budget search) pays no per-netlist XLA trace+compile. Bit-exact vs
    `Simulator.run` by the engine's tested contract."""
    from repro.core import minimize as MZ
    from repro.kernels.netlist_sim import pack_population, population_accuracy
    xq = MZ.quantize_inputs(c, x)
    acc = population_accuracy(pack_population([net]), np.asarray(xq),
                              np.asarray(y))
    return float(acc[0])
