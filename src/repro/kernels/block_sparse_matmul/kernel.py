"""Block-sparse matmul: zero (bk, bn) weight tiles are skipped via pl.when.

The paper's unstructured pruning adapted to the MXU (DESIGN.md §3): scalar
zeros can't be skipped by a systolic array, but a zeroed VMEM *tile* can —
both its HBM fetch and its MXU issue are guarded by the block mask. FLOPs
and weight bytes scale with (1 - block_sparsity), matching the bespoke
circuit's deleted-multiplier semantics at tile granularity.

Note on the HBM fetch: with standard BlockSpec prefetch the w tile is still
DMA'd; a production version uses scalar-prefetch grid remapping to also skip
the DMA (documented EXPERIMENTS.md §Perf) — the MXU-skip is what pl.when
delivers portably.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _bsmm_kernel(mask_ref, x_ref, w_ref, o_ref, acc_ref, *, k_steps: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(mask_ref[0, 0] > 0)
    def _compute():
        acc_ref[...] += jax.lax.dot_general(
            x_ref[...].astype(jnp.float32), w_ref[...].astype(jnp.float32),
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def block_sparse_matmul_pallas(x, w, block_mask, *, block_m: int = 128,
                               block_n: int = 128, block_k: int = 128,
                               interpret: bool = False):
    """x: (M, K); w: (K, N); block_mask: (K//bk, N//bn) int32 (1 = live)."""
    M, K = x.shape
    K2, N = w.shape
    assert K == K2
    assert M % block_m == 0 and N % block_n == 0 and K % block_k == 0
    assert block_mask.shape == (K // block_k, N // block_n)
    k_steps = K // block_k
    grid = (M // block_m, N // block_n, k_steps)

    return pl.pallas_call(
        functools.partial(_bsmm_kernel, k_steps=k_steps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1), lambda i, j, k: (k, j)),
            pl.BlockSpec((block_m, block_k), lambda i, j, k: (i, k)),
            pl.BlockSpec((block_k, block_n), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(block_mask.astype(jnp.int32), x, w)
