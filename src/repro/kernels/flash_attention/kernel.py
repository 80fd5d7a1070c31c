"""Flash attention (online softmax), causal + sliding-window.

The memory-roofline fix for the attention-heavy cells: the (bq, bk) score
tile lives only in VMEM — HBM never sees the O(T*S) score matrix that
dominates `bytes accessed` in the chunked-jnp path (EXPERIMENTS.md §Perf).

Grid: (B*H, T/bq, S/bk), k innermost. Causal block skipping: KV blocks
strictly above the diagonal (and, with a window, strictly below the band)
contribute nothing and are skipped via pl.when — FLOPs drop ~2x for causal,
~T/(2W)x for sliding windows.

Running max m, denominator l and output accumulator live in VMEM scratch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1.0e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  k_steps: int, block_q: int, block_k: int, causal: bool,
                  window: int, scale: float, softcap: float):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = qi * block_q
    k_start = ki * block_k
    # visibility of this KV block for this Q block
    visible = True
    if causal:
        visible = k_start <= q_start + block_q - 1
    if window:
        visible = jnp.logical_and(
            visible, k_start + block_k - 1 > q_start - window)

    @pl.when(visible)
    def _attend():
        q = q_ref[0].astype(jnp.float32)                   # (bq, d)
        k = k_ref[0].astype(jnp.float32)                   # (bk, d)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if softcap:
            s = jnp.tanh(s / softcap) * softcap
        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32,
                                                   (block_q, block_k), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                                   (block_q, block_k), 1)
        ok = jnp.ones((block_q, block_k), jnp.bool_)
        if causal:
            ok &= k_pos <= q_pos
        if window:
            ok &= k_pos > q_pos - window
        s = jnp.where(ok, s, NEG_INF)

        m_prev = m_ref[...]                                # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)                             # (bq, bk)
        p = jnp.where(ok, p, 0.0)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        v = v_ref[0].astype(jnp.float32)                   # (bk, d)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == k_steps - 1)
    def _flush():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def flash_attention_pallas(q, k, v, *, causal: bool = True, window: int = 0,
                           softcap: float = 0.0, block_q: int = 128,
                           block_k: int = 128, interpret: bool = False):
    """q: (BH, T, d); k/v: (BH, S, d). GQA callers fold/broadcast heads in
    ops.py. T % block_q == 0, S % block_k == 0 (ops.py pads)."""
    BH, T, d = q.shape
    S = k.shape[1]
    assert T % block_q == 0 and S % block_k == 0
    k_steps = S // block_k
    grid = (BH, T // block_q, k_steps)
    scale = d ** -0.5

    return pl.pallas_call(
        functools.partial(_flash_kernel, k_steps=k_steps, block_q=block_q,
                          block_k=block_k, causal=causal, window=window,
                          scale=scale, softcap=softcap),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, T, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
