"""Quantized matmul: y = x @ (w_q * scale), dequantized tile-by-tile in VMEM.

The paper's quantization on TPU (DESIGN.md §3): weights live in HBM at
`bits`/8 bytes each; the (bk, bn) int tile is streamed to VMEM, dequantized
on the VPU against per-column scales, and fed to the MXU in fp32/bf16. HBM
traffic for weights drops by 2/(bits/8)x vs bf16 — the decode-roofline win.

Grid: (M/bm, N/bn, K/bk), k innermost ("arbitrary" semantics), fp32
accumulator in VMEM scratch, output written on the last k step.
Block shapes are MXU-aligned (multiples of (8,128) tiles).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _qmm_kernel(x_ref, w_ref, s_ref, o_ref, acc_ref, *, k_steps: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w = w_ref[...].astype(jnp.float32) * s_ref[...].astype(jnp.float32)
    acc_ref[...] += jax.lax.dot_general(
        x_ref[...].astype(jnp.float32), w,
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def quant_matmul_pallas(x, w_q, scales, *, block_m: int = 128,
                        block_n: int = 128, block_k: int = 128,
                        interpret: bool = False):
    """x: (M, K) float; w_q: (K, N) int8 on a `bits` grid; scales: (N,) f32.
    M, K, N must be multiples of the block sizes (ops.py pads)."""
    M, K = x.shape
    K2, N = w_q.shape
    assert K == K2 and scales.shape == (N,)
    assert M % block_m == 0 and N % block_n == 0 and K % block_k == 0
    k_steps = K // block_k
    grid = (M // block_m, N // block_n, k_steps)

    return pl.pallas_call(
        functools.partial(_qmm_kernel, k_steps=k_steps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, k: (i, k)),
            pl.BlockSpec((block_k, block_n), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, block_n), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, w_q, scales.reshape(1, N))
