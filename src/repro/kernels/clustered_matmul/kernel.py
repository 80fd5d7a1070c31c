"""Clustered (codebook) matmul: y = x @ W where W[k, n] = codebook[k, idx[k, n]].

The paper's weight clustering on TPU (DESIGN.md §3): HBM stores one small int
index per weight (ceil(log2(n_clusters)) bits; int8 here) plus per-row
codebooks. The (bk, bn) weight tile is *reconstructed in VMEM* via a one-hot
contraction against the codebook tile — MXU-friendly (a (bn, C) x (C,) row
product per k), no lane gathers. HBM weight traffic: 1 byte/weight + tiny
codebooks instead of 2 bytes/weight, independent of cluster count.

Per-input-row codebooks ((K, C)) exactly mirror `core.clustering`'s
multiplier-sharing form.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _cmm_kernel(x_ref, idx_ref, cb_ref, o_ref, acc_ref, *, k_steps: int,
                n_clusters: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    idx = idx_ref[...]                                    # (bk, bn) int
    cb = cb_ref[...].astype(jnp.float32)                  # (bk, C)
    # one-hot reconstruction: w[k, n] = sum_c (idx[k,n]==c) * cb[k,c]
    iota = jax.lax.broadcasted_iota(jnp.int32, idx.shape + (n_clusters,), 2)
    onehot = (idx[..., None] == iota).astype(jnp.float32)  # (bk, bn, C)
    w = jnp.sum(onehot * cb[:, None, :], axis=-1)          # (bk, bn)
    acc_ref[...] += jax.lax.dot_general(
        x_ref[...].astype(jnp.float32), w,
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def clustered_matmul_pallas(x, idx, codebook, *, block_m: int = 128,
                            block_n: int = 128, block_k: int = 128,
                            interpret: bool = False):
    """x: (M, K); idx: (K, N) int8/int32; codebook: (K, C) f32."""
    M, K = x.shape
    K2, N = idx.shape
    C = codebook.shape[1]
    assert K == K2 and codebook.shape[0] == K
    assert M % block_m == 0 and N % block_n == 0 and K % block_k == 0
    k_steps = K // block_k
    grid = (M // block_m, N // block_n, k_steps)

    return pl.pallas_call(
        functools.partial(_cmm_kernel, k_steps=k_steps, n_clusters=C),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, k: (i, k)),
            pl.BlockSpec((block_k, block_n), lambda i, j, k: (k, j)),
            pl.BlockSpec((block_k, C), lambda i, j, k: (k, 0)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, idx.astype(jnp.int32), codebook)
