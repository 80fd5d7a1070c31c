"""Selective-scan (Mamba-1) Pallas kernel: the time loop runs INSIDE the
kernel with the recurrent state resident in VMEM scratch.

This is the TPU-native analogue of the CUDA selective_scan kernel (DESIGN.md
§3): the HBM-visible traffic is exactly the inputs/outputs (u, dt, B, C -> y);
the (bd, N) state h never leaves VMEM. The pure-JAX `lax.scan` path
(repro.nn.ssm) round-trips the carry per step on non-fused backends — this
kernel is what the falcon-mamba roofline projects onto for the TPU target.

Grid: (B, d/bd, T/bt). The T axis is sequential ("arbitrary" semantics); the
carry persists in scratch across the T-grid steps of the same (b, d-block).
Within a block, bt time steps unroll (bt small: the recurrence is serial).

  h_t = exp(dt_t * A) * h_{t-1} + (dt_t * u_t) B_t ;  y_t = h_t . C_t + D u_t
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssm_kernel(u_ref, dt_ref, b_ref, c_ref, a_ref, d_ref, y_ref, h_ref, *,
                bt: int):
    ti = pl.program_id(2)

    @pl.when(ti == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    a = a_ref[...]                                   # (bd, N) fp32
    d = d_ref[...]                                   # (1, bd)
    h = h_ref[...]                                   # (bd, N)
    for t in range(bt):                              # serial recurrence
        u_t = u_ref[0, t, :].astype(jnp.float32)     # (bd,)
        dt_t = dt_ref[0, t, :].astype(jnp.float32)   # (bd,)
        b_t = b_ref[0, t, :].astype(jnp.float32)     # (N,)
        c_t = c_ref[0, t, :].astype(jnp.float32)     # (N,)
        da = jnp.exp(dt_t[:, None] * a)              # (bd, N)
        h = da * h + (dt_t * u_t)[:, None] * b_t[None, :]
        y = jnp.sum(h * c_t[None, :], axis=1) + d[0] * u_t
        y_ref[0, t, :] = y.astype(y_ref.dtype)
    h_ref[...] = h


def ssm_scan_pallas(u, dt, B_, C_, A, D, *, block_d: int = 512,
                    block_t: int = 8, interpret: bool = False):
    """u/dt: (B, T, d); B_/C_: (B, T, N); A: (d, N) (negative); D: (d,).
    Returns y (B, T, d). d % block_d == 0, T % block_t == 0 (ops.py pads T)."""
    Bsz, T, d = u.shape
    N = A.shape[1]
    assert d % block_d == 0 and T % block_t == 0
    grid = (Bsz, d // block_d, T // block_t)

    return pl.pallas_call(
        functools.partial(_ssm_kernel, bt=block_t),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_t, block_d), lambda b, i, t: (b, t, i)),
            pl.BlockSpec((1, block_t, block_d), lambda b, i, t: (b, t, i)),
            pl.BlockSpec((1, block_t, N), lambda b, i, t: (b, t, 0)),
            pl.BlockSpec((1, block_t, N), lambda b, i, t: (b, t, 0)),
            pl.BlockSpec((block_d, N), lambda b, i, t: (i, 0)),
            pl.BlockSpec((1, block_d), lambda b, i, t: (0, i)),
        ],
        out_specs=pl.BlockSpec((1, block_t, block_d),
                               lambda b, i, t: (b, t, i)),
        out_shape=jax.ShapeDtypeStruct((Bsz, T, d), u.dtype),
        scratch_shapes=[pltpu.VMEM((block_d, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(u, dt, B_, C_, A.astype(jnp.float32),
      D.astype(jnp.float32).reshape(1, d))
