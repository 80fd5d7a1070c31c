"""Printed-MLP minimization pipeline (the paper, end to end).

Flow per candidate spec (bits/sparsity/clusters per layer):

  FP32 pretrain (cached per dataset)
    -> magnitude masks from pretrained weights (fixed during finetune)
    -> QAT finetune with STE prune/cluster/quant forward   [paper's QKeras QAT]
    -> bespoke "compile": integer weights + shared-product codebooks
    -> test accuracy of the compiled arithmetic + printed area (hw_model)

This module holds the training loop, the pretrain, the host half of the
bespoke compile and the compiled arithmetic. The finetune and the bespoke
compile's device half run for whole populations in `core.batch_eval`;
`evaluate_spec` and the Fig. 1 sweeps are calls of its
`evaluate_population`, and `core.ga` drives the combined search of
Fig. 2 through it.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.printed_mlp import PrintedMLPConfig
from repro.core import hw_model as HW
from repro.core import pruning as P
from repro.core.compression_spec import LayerMin, ModelMin
from repro.data.uci import dataset_for
from repro.nn import mlp as M
from repro.obs import metrics as MT
from repro.obs import trace as TR


# ---------------------------------------------------------------------------
# training
# ---------------------------------------------------------------------------


def _adam_update(g, m, v, t, lr, b1=0.9, b2=0.999, eps=1e-8):
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * jnp.square(g)
    mh = m / (1 - b1 ** t)
    vh = v / (1 - b2 ** t)
    return -lr * mh / (jnp.sqrt(vh) + eps), m, v


def _loss(params, x, y, w_transform):
    p2 = {"layers": tuple(
        {"w": w_transform(i, l["w"]), "b": l["b"]}
        for i, l in enumerate(params["layers"]))}
    logits = M.mlp_forward(p2, x)
    logp = jax.nn.log_softmax(logits)
    return -jnp.mean(jnp.take_along_axis(logp, y[:, None], 1))


def _train(params, x, y, *, epochs: int, lr: float, w_transform):
    flat, treedef = jax.tree_util.tree_flatten(params)
    m0 = [jnp.zeros_like(l) for l in flat]

    def epoch(carry, t):
        flat, m, v = carry
        params = jax.tree_util.tree_unflatten(treedef, flat)
        g = jax.grad(_loss)(params, x, y, w_transform)
        gflat = jax.tree_util.tree_leaves(g)
        upd = [_adam_update(gi, mi, vi, t + 1, lr)
               for gi, mi, vi in zip(gflat, m, v)]
        flat = [f + u[0] for f, u in zip(flat, upd)]
        return (flat, [u[1] for u in upd], [u[2] for u in upd]), None

    (flat, _, _), _ = jax.lax.scan(
        epoch, (flat, m0, list(m0)), jnp.arange(epochs, dtype=jnp.float32))
    return jax.tree_util.tree_unflatten(treedef, flat)


@functools.lru_cache(maxsize=32)
def pretrain(cfg: PrintedMLPConfig, *, epochs: int = 600, lr: float = 5e-3,
             seed: int = 0):
    """FP32 baseline training (cached). Returns (params, (data tuple))."""
    xtr, ytr, xte, yte = dataset_for(cfg, seed=seed)
    params = M.mlp_init(jax.random.PRNGKey(seed), cfg.layer_dims)
    fit = jax.jit(functools.partial(
        _train, epochs=epochs, lr=lr, w_transform=lambda i, w: w))
    params = fit(params, jnp.asarray(xtr), jnp.asarray(ytr))
    return params, (xtr, ytr, xte, yte)


# ---------------------------------------------------------------------------
# bespoke compile + evaluation
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class CompiledMLP:
    q_layers: List[np.ndarray]           # integer weights (0 = pruned)
    scales: List[float]
    biases: List[np.ndarray]
    clusters: List[Optional[Tuple[np.ndarray, np.ndarray]]]  # (idx, int codebook)
    w_bits: List[int]
    input_bits: int

    def dense_weights(self) -> List[np.ndarray]:
        out = []
        for q, s, cl in zip(self.q_layers, self.scales, self.clusters):
            out.append(q.astype(np.float32) * s)
        return out


def compile_bespoke(params, spec: ModelMin, masks,
                    prefetched=None) -> CompiledMLP:
    """Integer weights, codebooks and scales of one candidate.

    The device half, the per-input k-means and the quantizer, is
    `batch_eval._population_bespoke`; the rest is numpy on its outputs
    (`_snap_bespoke`). ``prefetched`` is this candidate's slice of that
    launch's fetched outputs, when the population path ran it for the
    whole population (counted in ``eval.bespoke_prefetched``); without it
    the launch runs here at P = 1."""
    with TR.span("minimize.compile_bespoke"):
        if prefetched is None:
            from repro.core import batch_eval as BE  # lazy: imports us
            prefetched = BE.bespoke_one(params, spec, masks)
        else:
            MT.counter("eval.bespoke_prefetched").inc()
        return _snap_bespoke(params, spec, masks, prefetched)


def _snap_bespoke(params, spec: ModelMin, masks, dev) -> CompiledMLP:
    """Host half of `compile_bespoke`: snap codebooks to the fixed-point
    grid, re-apply pruning zeros, take the biases. ``dev``: per layer the
    candidate's `batch_eval._population_bespoke` outputs, host arrays."""
    q_layers, scales, biases, clusters, w_bits = [], [], [], [], []
    for i, layer in enumerate(params["layers"]):
        lm = spec.layers[i]
        bits = lm.bits if lm.bits is not None else 8
        if lm.clusters is not None:
            cb, idx = dev[i]["cb"][:, :lm.clusters], dev[i]["idx"]
            w_rec = np.take_along_axis(cb, idx, axis=1)
            # snap codebooks to the fixed-point grid
            qmax = 2 ** (bits - 1) - 1
            s = max(np.abs(w_rec).max(), 1e-8) / qmax
            cb_q = np.clip(np.round(cb / s), -qmax, qmax).astype(np.int64)
            q = np.take_along_axis(cb_q, idx, axis=1)
            # re-apply pruning zeros (cluster may absorb them)
            if masks[i] is not None:
                q = q * np.asarray(masks[i], np.int64)
            clusters.append((idx, cb_q))
        else:
            q, s = np.asarray(dev[i]["q"], np.int64), float(dev[i]["scale"])
            clusters.append(None)
        q_layers.append(q)
        scales.append(float(s))
        biases.append(np.asarray(layer["b"], np.float32))
        w_bits.append(bits)
    return CompiledMLP(q_layers, scales, biases, clusters, w_bits,
                       spec.input_bits)


def quantize_inputs(c: CompiledMLP, x: np.ndarray) -> np.ndarray:
    """ADC front-end: features in [0, 1] -> unsigned integers on the
    2**input_bits - 1 grid — the same rounding `compiled_accuracy` applies
    before its float emulation, kept integer."""
    levels = (1 << c.input_bits) - 1
    return np.round(np.asarray(x, np.float32) * levels).astype(np.int64)


def integer_biases(c: CompiledMLP) -> List[np.ndarray]:
    """Bias constants on each layer's integer accumulator grid.

    Layer i's integer pre-activation represents the float one through the
    cumulative factor alpha_i = (prod_{j<=i} scale_j) / (2**input_bits - 1)
    (inputs contribute 1/levels, each weight matmul its layer scale), so the
    hardwired bias constant is round(b / alpha_i). ReLU and argmax commute
    with the positive alpha_i, making this the only rounding the bespoke
    integer circuit adds on top of the QAT compile."""
    alpha = 1.0 / ((1 << c.input_bits) - 1)
    out = []
    for i, (s, b) in enumerate(zip(c.scales, c.biases)):
        alpha *= s
        v = np.round(np.asarray(b, np.float64) / alpha)
        if np.abs(v).max(initial=0.0) >= 2.0 ** 62:
            raise OverflowError(
                f"layer {i} bias constant exceeds the 62-bit exact integer "
                f"budget (degenerate scale chain alpha={alpha:.3e})")
        out.append(v.astype(np.int64))
    return out


def integer_forward(c: CompiledMLP, x_int: np.ndarray
                    ) -> Tuple[List[np.ndarray], np.ndarray]:
    """The static QAT forward in exact integer arithmetic — the reference
    semantics the compiled netlist (`repro.circuit`) must reproduce
    bit-for-bit.

    x_int: (B, d_in) integers from `quantize_inputs`. Returns (per-layer
    integer pre-activations [(B, d_out_i) int64], argmax class (B,)).
    """
    b_ints = integer_biases(c)
    a = np.asarray(x_int, np.int64)
    pres: List[np.ndarray] = []
    for i, (q, b) in enumerate(zip(c.q_layers, b_ints)):
        pre = a @ q.astype(np.int64) + b
        pres.append(pre)
        if i < len(c.q_layers) - 1:
            a = np.maximum(pre, 0)
    return pres, np.argmax(pres[-1], axis=1)


def compiled_accuracy(c: CompiledMLP, x: np.ndarray, y: np.ndarray) -> float:
    """Accuracy of the exact bespoke arithmetic: quantized inputs x quantized
    integer weights (float emulation is exact for these ranges)."""
    levels = 2 ** c.input_bits - 1
    h = np.round(np.asarray(x, np.float32) * levels) / levels
    ws = c.dense_weights()
    for i, (w, b) in enumerate(zip(ws, c.biases)):
        h = h @ w + b
        if i < len(ws) - 1:
            h = np.maximum(h, 0.0)
    return float(np.mean(np.argmax(h, axis=1) == y))


def compiled_cost(c: CompiledMLP) -> HW.CircuitCost:
    return HW.mlp_cost(c.q_layers, w_bits=c.w_bits, in_bits=c.input_bits,
                       clusters=c.clusters)


# ---------------------------------------------------------------------------
# spec evaluation + sweeps (Fig. 1)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class EvalResult:
    spec: ModelMin
    accuracy: float
    area_mm2: float
    power_mw: float
    n_multipliers: int
    # critical-path length of the compiled netlist in full-adder-stage
    # delays (repro.circuit) — the analytic model cannot produce this;
    # None for results predating the circuit compiler (old caches).
    delay_levels: Optional[int] = None


def make_masks(params0, spec: ModelMin):
    return [P.magnitude_mask(l["w"], lm.sparsity) if lm.sparsity > 0 else None
            for l, lm in zip(params0["layers"], spec.layers)]


def evaluate_spec(cfg: PrintedMLPConfig, spec: ModelMin, *,
                  epochs: int = 150, seed: int = 0,
                  netlist: bool = True) -> EvalResult:
    """One spec through the population path:
    `batch_eval.evaluate_population(cfg, [spec], ...)[0]`. Accuracy
    defaults to the bit-exact simulation of the compiled netlist (the
    printed datapath); ``netlist=False`` is the analytic float-emulation
    opt-out, and approximated specs are always scored on their
    approximated netlist. A spec whose evaluation fails is retried once,
    then quarantined with worst-case fitness and a warning."""
    from repro.core import batch_eval as BE      # lazy: imports us
    return BE.evaluate_population(cfg, [spec], epochs=epochs, seed=seed,
                                  netlist=netlist)[0]


def baseline(cfg: PrintedMLPConfig, *, seed: int = 0) -> EvalResult:
    """MICRO'20 un-minimized bespoke MLP: dense 8-bit fixed point."""
    n = len(cfg.layer_dims) - 1
    return evaluate_spec(cfg, ModelMin.uniform(n, bits=8), epochs=60,
                         seed=seed)


def quant_sweep(cfg, bits_range=None, *, epochs=150, seed=0):
    if bits_range is None:
        bits_range = range(2, 8)
    from repro.core import batch_eval as BE
    n = len(cfg.layer_dims) - 1
    return BE.evaluate_population(
        cfg, [ModelMin.uniform(n, bits=b) for b in bits_range],
        epochs=epochs, seed=seed)


def prune_sweep(cfg, sparsities=(0.2, 0.3, 0.4, 0.5, 0.6), *, epochs=150,
                seed=0):
    from repro.core import batch_eval as BE
    n = len(cfg.layer_dims) - 1
    return BE.evaluate_population(
        cfg, [ModelMin.uniform(n, bits=8, sparsity=s) for s in sparsities],
        epochs=epochs, seed=seed)


def cluster_sweep(cfg, ks=(2, 3, 4, 6, 8), *, epochs=150, seed=0):
    from repro.core import batch_eval as BE
    n = len(cfg.layer_dims) - 1
    return BE.evaluate_population(
        cfg, [ModelMin.uniform(n, bits=8, clusters=k) for k in ks],
        epochs=epochs, seed=seed)
