"""Weight clustering (paper §II-C, mechanism of Han et al. Deep Compression).

Two granularities:

* ``kmeans_layer`` — classic per-layer k-means codebook (Deep Compression).
* ``cluster_per_input`` — the paper's *hardware* form: weights in the same
  input row (i.e. multiplied by the same input x_i) are forced to shared
  values, so the bespoke circuit computes each product x_i * c once and fans
  it out. The number of *multipliers* for input i collapses from fan-out to
  (#distinct clusters in row i).

Both return (codebook, indices) plus helpers to reconstruct weights, an STE
reconstruction for cluster-aware retraining, and multiplier statistics
consumed by the printed-area model.

TPU adaptation: per-tile codebooks (``kernels/clustered_matmul``) — the
shareable unit on TPU is an HBM->VMEM transfer, not a product wire.
"""
from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp


# ---------------------------------------------------------------------------
# 1-D k-means (weights are scalars -> exact-ish via sorted init + Lloyd)
# ---------------------------------------------------------------------------


def _kmeans_1d(x: jnp.ndarray, k: int, iters: int = 25):
    """x: (N,) fp32. Returns (centroids (k,), assign (N,) int32).
    Deterministic: quantile init + Lloyd iterations (jit-friendly).

    Deliberately NOT jitted: this is the eager numerical reference that
    `batch_eval._padded_kmeans_1d` is tested bit-exact against (a fused
    standalone executable rounds differently by ~1 ulp). The hot eager
    entry is `cluster_per_input`, which owns the jit boundary."""
    qs = (jnp.arange(k, dtype=jnp.float32) + 0.5) / k
    cent = jnp.quantile(x, qs)

    def step(cent, _):
        d = jnp.abs(x[:, None] - cent[None, :])            # (N,k)
        a = jnp.argmin(d, axis=1)
        one = jax.nn.one_hot(a, k, dtype=jnp.float32)       # (N,k)
        cnt = one.sum(0)
        s = (one * x[:, None]).sum(0)
        new = jnp.where(cnt > 0, s / jnp.maximum(cnt, 1), cent)
        return new, None

    cent, _ = jax.lax.scan(step, cent, None, length=iters)
    a = jnp.argmin(jnp.abs(x[:, None] - cent[None, :]), axis=1)
    return cent, a.astype(jnp.int32)


def kmeans_layer(w: jnp.ndarray, k: int, iters: int = 25):
    """Per-layer codebook. Returns (codebook (k,), idx w.shape int32)."""
    flat = w.astype(jnp.float32).reshape(-1)
    cent, a = _kmeans_1d(flat, k, iters)
    return cent, a.reshape(w.shape)


@partial(jax.jit, static_argnames=("k", "iters"))
def cluster_per_input(w: jnp.ndarray, k: int, iters: int = 25):
    """Paper's multiplier-sharing form: k-means per input row.
    w: (d_in, d_out). Returns (codebooks (d_in, k), idx (d_in, d_out)).

    Jitted with static (k, iters), so an eager call (``clustering_error``)
    builds one executable per (shape, k) instead of retracing the Lloyd
    ``lax.scan`` on every call (the netlist_bench zero-compile gate once
    attributed ~14 backend compiles per generation to this site)."""
    f = jax.vmap(lambda row: _kmeans_1d(row, k=k, iters=iters))
    cent, a = f(w.astype(jnp.float32))
    return cent, a


def reconstruct_layer(codebook: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    return jnp.take(codebook, idx)


def reconstruct_per_input(codebooks: jnp.ndarray, idx: jnp.ndarray):
    """codebooks (d_in, k), idx (d_in, d_out) -> w (d_in, d_out)."""
    return jnp.take_along_axis(codebooks, idx, axis=1)


def cluster_ste(w: jnp.ndarray, k: int, *, per_input: bool = True):
    """Cluster-aware training forward: snap to current codebook, identity
    gradient (Deep Compression fine-tunes the shared values; STE over the
    assignment is the standard relaxation)."""
    wd = jax.lax.stop_gradient(w)
    if per_input and w.ndim == 2:
        cb, idx = cluster_per_input(wd, k)
        wq = reconstruct_per_input(cb, idx)
    else:
        cb, idx = kmeans_layer(wd, k)
        wq = reconstruct_layer(cb, idx)
    return w + (wq.astype(w.dtype) - jax.lax.stop_gradient(w))


# ---------------------------------------------------------------------------
# hardware statistics
# ---------------------------------------------------------------------------


def multipliers_needed(idx: jnp.ndarray, codebooks: jnp.ndarray) -> int:
    """Bespoke multiplier count after per-input sharing: for each input row,
    one multiplier per *distinct, non-zero* cluster actually used."""
    d_in, k = codebooks.shape
    used = jax.vmap(lambda row: jax.nn.one_hot(row, k).max(0))(idx)  # (d_in,k)
    nonzero = jnp.abs(codebooks) > 1e-8
    return int(jnp.sum(used * nonzero))


def clustering_error(w: jnp.ndarray, k: int, *, per_input: bool = True) -> float:
    if per_input and w.ndim == 2:
        cb, idx = cluster_per_input(w, k)
        wq = reconstruct_per_input(cb, idx)
    else:
        cb, idx = kmeans_layer(w, k)
        wq = reconstruct_layer(cb, idx)
    return float(jnp.linalg.norm(w - wq) /
                 jnp.maximum(jnp.linalg.norm(w), 1e-9))
