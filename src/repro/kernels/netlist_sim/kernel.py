"""Pallas population netlist-sim kernel.

Grid: (P candidates, B/block_b input tiles) — every cell owns one
candidate's whole dense node table (VMEM, (1, N) blocks) and one tile of
inputs. Levels run as an *unrolled scan* inside the kernel: per level l the
slot window [level_ptr[l], level_ptr[l+1]) is selected by an iota mask and
the whole table's candidate results are computed branchlessly (nested
``jnp.where`` over the opcode lane) — only in-window compute slots commit.
Within a level every operand slot lives in a strictly earlier level, so a
full-table masked update per level is dependency-safe.

Lanes are int32: ops.py routes populations whose verifier width bound
exceeds 32 to the jnp levels engine instead (TPU Pallas has no int64
lanes). Off-TPU the kernel runs interpret=True like the other five kernels
— the bit-exactness contract is identical in both modes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.circuit import ir

_SHL = int(ir.Op.SHL)
_ADD = int(ir.Op.ADD)
_SUB = int(ir.Op.SUB)
_NEG = int(ir.Op.NEG)
_RELU = int(ir.Op.RELU)
_ARGMAX = int(ir.Op.ARGMAX)


def _sim_kernel(op_ref, a_ref, b_ref, sh_ref, val_ref, ptr_ref, inp_ref,
                am_ref, x_ref, o_ref, *, n_levels: int):
    N = op_ref.shape[1]
    bb = x_ref.shape[1]
    n_in = x_ref.shape[2]
    C = o_ref.shape[2]
    opc = op_ref[0, :]                                   # (N,)
    slot = jax.lax.broadcasted_iota(jnp.int32, (N,), 0)
    # executable slots: SHL..TRUNC minus ARGMAX (CONST/INPUT are seeds)
    is_comp = (opc >= _SHL) & (opc != _ARGMAX)

    # seed: CONST payloads everywhere (non-const slots carry 0), then the
    # ADC lanes — dynamic scalar columns via one-hot masks (n_in is small)
    vals = jnp.broadcast_to(val_ref[0, :][None, :], (bb, N)).astype(jnp.int32)
    for i in range(n_in):
        col = inp_ref[0, i]
        vals = jnp.where((slot == col)[None, :], x_ref[0, :, i][:, None],
                         vals)

    for lvl in range(n_levels):
        lo = ptr_ref[0, lvl]
        hi = ptr_ref[0, lvl + 1]
        a = jnp.take(vals, a_ref[0, :], axis=1)          # (bb, N)
        b = jnp.take(vals, b_ref[0, :], axis=1)
        sh = sh_ref[0, :][None, :]
        r = jnp.where(opc == _SHL, jnp.left_shift(a, sh),
            jnp.where(opc == _ADD, a + b,
            jnp.where(opc == _SUB, a - b,
            jnp.where(opc == _NEG, -a,
            jnp.where(opc == _RELU, jnp.maximum(a, 0),
                      # TRUNC: arithmetic floor-truncate of the low bits
                      jnp.left_shift(jnp.right_shift(a, sh), sh))))))
        active = is_comp & (slot >= lo) & (slot < hi)
        vals = jnp.where(active[None, :], r, vals)

    # the comparator tree's operand gather (C dynamic columns, one-hot)
    cols = []
    for j in range(C):
        col = am_ref[0, j]
        cols.append(jnp.sum(jnp.where((slot == col)[None, :], vals, 0),
                            axis=1))
    o_ref[0, :, :] = jnp.stack(cols, axis=1)


def netlist_sim_pallas(op, arg_a, arg_b, shift, val, level_ptr, input_pos,
                       argmax_pos, x, *, block_b: int = 256,
                       interpret: bool = False):
    """Tables: (P, N) int32 (``val`` included — int32 lanes only);
    level_ptr: (P, L+1); input_pos: (P, n_in); argmax_pos: (P, C);
    x: (P, B, n_in) int32 with B a multiple of block_b (ops.py pads).
    -> (P, B, C) int32 comparator operands."""
    P, N = op.shape
    Lp1 = level_ptr.shape[1]
    B, n_in = x.shape[1], x.shape[2]
    C = argmax_pos.shape[1]
    assert B % block_b == 0, (B, block_b)
    grid = (P, B // block_b)

    row = pl.BlockSpec((1, N), lambda p, t: (p, 0))
    return pl.pallas_call(
        functools.partial(_sim_kernel, n_levels=Lp1 - 1),
        grid=grid,
        in_specs=[
            row, row, row, row, row,
            pl.BlockSpec((1, Lp1), lambda p, t: (p, 0)),
            pl.BlockSpec((1, n_in), lambda p, t: (p, 0)),
            pl.BlockSpec((1, C), lambda p, t: (p, 0)),
            pl.BlockSpec((1, block_b, n_in), lambda p, t: (p, t, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_b, C), lambda p, t: (p, t, 0)),
        out_shape=jax.ShapeDtypeStruct((P, B, C), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(op, arg_a, arg_b, shift, val, level_ptr, input_pos, argmax_pos, x)
