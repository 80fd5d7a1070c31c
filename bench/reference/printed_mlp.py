"""Plain reference for one candidate evaluation of the printed-MLP search.

Straightforward per-candidate code, written from the published recipe
(Mubarik et al., MICRO'20 bespoke printed MLPs; QKeras-style QAT) and
independent of the program under test: it imports nothing from `repro`
and takes nothing the program made. From a configuration file, a seed and
one genome it builds the synthetic stand-in data, the float pretrain, the
magnitude masks, the QAT finetune (mask -> per-input k-means -> symmetric
fake-quant, all straight-through), the fixed-point compile, the exact
integer forward of the bespoke circuit and its analytic printed price. A
genome with approximation genes is instead the circuit after its
approximation passes, simulated and priced structurally
(`reference/approx.py`).

``dtype`` is the precision the float parts run in: float32 with the
configuration's matmul precision for the reference, bfloat16 for the
control (`bench/harness/check.py`). The integer circuit is exact in both.
"""
from __future__ import annotations

import functools
import json
import math
import zlib
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

# analytic EGT pricing constants of the configuration's cost model
AREA_FA_MM2 = 0.60
POWER_FA_MW = 0.004
RELU_FA_EQ = 2.0
ARGMAX_FA_EQ = 1.2
MULT_ROUTING_FACTOR = 2.0

_PRECISION = {"default": jax.lax.Precision.DEFAULT,
              "high": jax.lax.Precision.HIGH,
              "highest": jax.lax.Precision.HIGHEST}


# ---------------------------------------------------------------------------
# data and float training
# ---------------------------------------------------------------------------


def make_dataset(data: Dict, seed: int):
    """Class-conditional Gaussian mixture with the schema in ``data``
    (the configuration's ``assumed.dataset``): -> (xtr, ytr, xte, yte),
    features min-max scaled to [0, 1], a quarter held out for test."""
    rng = np.random.default_rng(
        seed + zlib.crc32(data["name"].encode()) % 65536)
    n_cls, n_feat = data["n_classes"], data["n_features"]
    freqs = data["imbalance"] ** np.arange(n_cls)
    freqs = freqs / freqs.sum()
    counts = np.maximum((freqs * data["n_samples"]).astype(int), 8)
    means = rng.normal(size=(n_cls, n_feat)) * data["class_sep"]
    mix = rng.normal(size=(n_feat, n_feat)) * 0.3
    xs, ys = [], []
    for c, n in enumerate(counts):
        z = rng.normal(size=(n, n_feat))
        x = means[c] + z @ (np.eye(n_feat) + mix) * data["noise"]
        x = x + 0.15 * np.sin(2.0 * x[:, ::-1])
        xs.append(x)
        ys.append(np.full(n, c, np.int32))
    x = np.concatenate(xs).astype(np.float32)
    y = np.concatenate(ys)
    lo, hi = x.min(axis=0), x.max(axis=0)
    x = (x - lo) / np.maximum(hi - lo, 1e-9)
    idx = rng.permutation(len(x))
    x, y = x[idx], y[idx]
    n_test = max(int(0.25 * len(x)), 16)
    return x[n_test:], y[n_test:], x[:n_test], y[:n_test]


def init_params(seed: int, dims) -> List[Dict]:
    """Glorot-uniform weights, zero biases, from ``PRNGKey(seed)``."""
    keys = jax.random.split(jax.random.PRNGKey(seed), len(dims) - 1)
    out = []
    for k, d_in, d_out in zip(keys, dims[:-1], dims[1:]):
        w = jax.random.uniform(k, (d_in, d_out), jnp.float32, -1.0, 1.0) \
            * math.sqrt(6.0 / (d_in + d_out))
        out.append({"w": w, "b": jnp.zeros((d_out,), jnp.float32)})
    return out


def _loss(layers, x, y, transform, precision):
    h = x
    for i, l in enumerate(layers):
        h = jnp.matmul(h, transform(i, l["w"]), precision=precision) + l["b"]
        if i < len(layers) - 1:
            h = jax.nn.relu(h)
    logp = jax.nn.log_softmax(h)
    return -jnp.mean(jnp.take_along_axis(logp, y[:, None], 1))


def _adam(g, m, v, t, lr, b1=0.9, b2=0.999, eps=1e-8):
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * jnp.square(g)
    mh = m / (1 - b1 ** t).astype(m.dtype)
    vh = v / (1 - b2 ** t).astype(v.dtype)
    return -lr * mh / (jnp.sqrt(vh) + eps), m, v


def _train(layers, x, y, *, epochs, lr, transform, precision):
    """Full-batch Adam, one step per epoch, in the dtype of ``layers``."""
    flat, tree = jax.tree_util.tree_flatten(layers)
    zeros = [jnp.zeros_like(a) for a in flat]

    def epoch(carry, t):
        flat, m, v = carry
        g = jax.tree_util.tree_leaves(jax.grad(_loss)(
            jax.tree_util.tree_unflatten(tree, flat), x, y, transform,
            precision))
        upd = [_adam(*a, t + 1, lr) for a in zip(g, m, v)]
        return ([f + u[0] for f, u in zip(flat, upd)],
                [u[1] for u in upd], [u[2] for u in upd]), None

    (flat, _, _), _ = jax.lax.scan(epoch, (flat, zeros, list(zeros)),
                                   jnp.arange(epochs, dtype=jnp.float32))
    return jax.tree_util.tree_unflatten(tree, flat)


def _identity(i, w):
    return w


@functools.partial(jax.jit, static_argnames=("epochs", "lr", "precision"))
def _pretrain_jit(layers, x, y, *, epochs, lr, precision):
    return _train(layers, x, y, epochs=epochs, lr=lr, transform=_identity,
                  precision=precision)


# ---------------------------------------------------------------------------
# the candidate's transforms (prune -> cluster -> quantize, all STE)
# ---------------------------------------------------------------------------


def magnitude_mask(w: np.ndarray, sparsity: float) -> Optional[np.ndarray]:
    """Keep the largest-|w| (1 - sparsity) share; ties at the threshold
    are kept. None when nothing is pruned."""
    if sparsity <= 0.0:
        return None
    a = np.abs(np.asarray(w, np.float32))
    k = max(int(round(a.size * (1.0 - sparsity))), 1)
    return a >= np.sort(a.reshape(-1))[-k]


def kmeans_1d(x, k: int, iters: int = 25):
    """Quantile-initialised Lloyd k-means of the scalars ``x`` ->
    (centroids (k,), assignment (N,)); first index wins a tie."""
    cent = jnp.quantile(x, (jnp.arange(k, dtype=jnp.float32) + 0.5) / k
                        ).astype(x.dtype)

    def step(cent, _):
        a = jnp.argmin(jnp.abs(x[:, None] - cent[None, :]), axis=1)
        one = jax.nn.one_hot(a, k, dtype=x.dtype)
        cnt = one.sum(0)
        s = (one * x[:, None]).sum(0)
        return jnp.where(cnt > 0, s / jnp.maximum(cnt, 1), cent), None

    cent, _ = jax.lax.scan(step, cent, None, length=iters)
    return cent, jnp.argmin(jnp.abs(x[:, None] - cent[None, :]), axis=1)


@functools.partial(jax.jit, static_argnames=("k",))
def cluster_rows(w, k: int):
    """Per-input-row k-means: -> (codebooks (d_in, k), idx (d_in, d_out))."""
    return jax.vmap(lambda row: kmeans_1d(row, k))(w.astype(jnp.float32))


def _fake_quant(w, bits: int):
    wd = jax.lax.stop_gradient(w)
    qmax = float(2 ** (bits - 1) - 1)
    scale = jnp.maximum(jnp.max(jnp.abs(wd)), 1e-8) / qmax
    return w + (jnp.clip(jnp.round(wd / scale), -qmax, qmax) * scale - wd)


def _cluster_ste(w, k: int):
    wd = jax.lax.stop_gradient(w)
    cb, idx = jax.vmap(lambda row: kmeans_1d(row, k))(wd)
    return w + (jnp.take_along_axis(cb, idx, axis=1).astype(w.dtype) - wd)


@functools.partial(jax.jit, static_argnames=("genes", "epochs", "lr",
                                             "precision"))
def _finetune_jit(layers, masks, x, y, *, genes, epochs, lr, precision):
    def transform(i, w):
        bits, clusters = genes[i]
        w = w * masks[i]
        if clusters is not None:
            w = _cluster_ste(w, clusters)
        if bits is not None:
            w = _fake_quant(w, bits)
        return w
    return _train(layers, x, y, epochs=epochs, lr=lr, transform=transform,
                  precision=precision)


# ---------------------------------------------------------------------------
# fixed-point compile, integer circuit, analytic price
# ---------------------------------------------------------------------------


def compile_fixed_point(layers, genes, masks):
    """Trained float weights -> integer coefficients on each layer's
    symmetric grid: [(q int64 (d_in, d_out), scale, bias, clusters or
    None, w_bits)]."""
    out = []
    for (w, b), (bits, k), mask in zip(layers, genes, masks):
        bits = 8 if bits is None else bits
        qmax = 2 ** (bits - 1) - 1
        w = np.asarray(w, np.float32)
        if mask is not None:
            w = w * mask.astype(np.float32)
        if k is not None:
            cb, idx = cluster_rows(jnp.asarray(w), k)
            cb, idx = np.asarray(cb), np.asarray(idx)
            w_rec = np.take_along_axis(cb, idx, axis=1)
            s = max(np.abs(w_rec).max(), 1e-8) / qmax
            cb_q = np.clip(np.round(cb / s), -qmax, qmax).astype(np.int64)
            q = np.take_along_axis(cb_q, idx, axis=1)
            if mask is not None:
                q = q * mask.astype(np.int64)
            clusters = (idx, cb_q)
        else:
            wj = jnp.asarray(w)
            sj = jnp.maximum(jnp.max(jnp.abs(wj)), 1e-8) / float(qmax)
            q = np.asarray(jnp.clip(jnp.round(wj / sj), -qmax, qmax)
                           .astype(jnp.int32), np.int64)
            s = np.asarray(sj)
            clusters = None
        out.append((q, float(s), np.asarray(b, np.float32), clusters, bits))
    return out


def integer_inputs(x, input_bits: int) -> np.ndarray:
    """ADC-quantised features: [0, 1] onto the 2**input_bits - 1 grid."""
    levels = (1 << input_bits) - 1
    return np.round(np.asarray(x, np.float32) * levels).astype(np.int64)


def integer_biases(comp, input_bits: int) -> List[np.ndarray]:
    """Each layer's bias rounded onto its accumulator's grid."""
    alpha = 1.0 / ((1 << input_bits) - 1)
    out = []
    for _, s, b, _, _ in comp:
        alpha *= s
        out.append(np.round(np.asarray(b, np.float64) / alpha)
                   .astype(np.int64))
    return out


def integer_accuracy(comp, input_bits: int, x, y) -> float:
    """Accuracy of the bespoke integer circuit: ADC-quantised inputs,
    integer coefficients, biases rounded onto each accumulator's grid,
    ReLU, argmax (first index wins a tie)."""
    a = integer_inputs(x, input_bits)
    for i, ((q, _, _, _, _), b_int) in enumerate(
            zip(comp, integer_biases(comp, input_bits))):
        pre = a @ q + b_int
        a = np.maximum(pre, 0) if i < len(comp) - 1 else pre
    return float(np.mean(np.argmax(a, axis=1) == np.asarray(y)))


def csd_digit_count(q: np.ndarray) -> np.ndarray:
    """Non-zero digits of the canonical signed-digit form of each |q|."""
    out = np.zeros(np.shape(q), np.int64)
    for i, c in np.ndenumerate(np.abs(np.asarray(q, np.int64))):
        c, n = int(c), 0
        while c:
            if c & 1:
                n += 1
                c = c + 1 if (c & 3) == 3 else c - 1
            c >>= 1
        out[i] = n
    return out


def price(comp, input_bits: int) -> Dict[str, float]:
    """Analytic bespoke printed cost in full-adder equivalents: CSD
    shift-add multipliers (shared per input row under clustering), one
    adder-tree operand per surviving product plus the bias add, ReLU
    comparators on hidden layers, an argmax comparator tree."""
    total, n_mult = 0.0, 0
    for i, (q, _, _, clusters, bits) in enumerate(comp):
        width = input_bits + bits
        active = q != 0
        if clusters is not None:
            idx, cb = clusters
            used = np.zeros(cb.shape, bool)
            for r in range(q.shape[0]):
                for c in range(q.shape[1]):
                    if active[r, c]:
                        used[r, idx[r, c]] = True
            used &= cb != 0
            n_mult += int(used.sum())
            total += float((csd_digit_count(cb) * used).sum() * width) \
                * MULT_ROUTING_FACTOR
        else:
            n_mult += int(active.sum())
            total += float(csd_digit_count(q).sum() * width) \
                * MULT_ROUTING_FACTOR
        operands = active.sum(axis=0)
        total += float((np.maximum(operands - 1, 0) + 1).sum() * width)
        if i < len(comp) - 1:
            fan_in = max(int(operands.max(initial=1)), 2)
            total += q.shape[1] * RELU_FA_EQ * (
                width + math.ceil(math.log2(fan_in)))
    last_bits = comp[-1][4]
    total += (comp[-1][0].shape[1] - 1) * ARGMAX_FA_EQ * (
        input_bits + last_bits + 4)
    return {"area_mm2": total * AREA_FA_MM2, "power_mw": total * POWER_FA_MW,
            "n_multipliers": n_mult}


# ---------------------------------------------------------------------------
# one deployment: data + pretrain once, then one genome at a time
# ---------------------------------------------------------------------------


class Reference:
    """The reference evaluator of one configuration at one data seed."""

    def __init__(self, config: Dict, seed: int, *, dtype=jnp.float32):
        self.cfg = config
        self.dtype = dtype
        self.precision = _PRECISION[config["precision"]["matmul"]]
        self.dims = tuple(config["layer_dims"])
        xtr, ytr, self.xte, self.yte = make_dataset(
            config["assumed"]["dataset"], seed)
        self.x = jnp.asarray(xtr, dtype)
        self.y = jnp.asarray(ytr)
        train = config["assumed"]["training"]
        layers = jax.tree_util.tree_map(lambda a: a.astype(dtype),
                                        init_params(seed, self.dims))
        self.params0 = _pretrain_jit(
            layers, self.x, self.y, epochs=train["pretrain_epochs"],
            lr=train["pretrain_lr"], precision=self.precision)

    def evaluate(self, spec_json: str, *, epochs: int,
                 lr: float) -> Dict[str, float]:
        """One genome (the search's spec JSON) -> accuracy and price; a
        genome with approximation genes is the circuit after its passes
        (`reference/approx.py`)."""
        from . import approx as AX
        spec = json.loads(spec_json)
        genes = tuple((g["bits"], g["clusters"]) for g in spec["layers"])
        w0 = [np.asarray(l["w"], np.float32) for l in self.params0]
        masks = [magnitude_mask(w, g["sparsity"])
                 for w, g in zip(w0, spec["layers"])]
        mask_arrays = tuple(
            jnp.ones(w.shape, self.dtype) if m is None
            else jnp.asarray(m, self.dtype) for w, m in zip(w0, masks))
        trained = _finetune_jit(self.params0, mask_arrays, self.x, self.y,
                                genes=genes, epochs=epochs, lr=lr,
                                precision=self.precision)
        layers = [(np.asarray(l["w"].astype(jnp.float32)),
                   np.asarray(l["b"].astype(jnp.float32))) for l in trained]
        comp = compile_fixed_point(layers, genes, masks)
        ib = spec["input_bits"]
        approx = AX.genes(spec)
        if approx is not None:
            return AX.evaluate(comp, ib, approx, integer_inputs(self.xte, ib),
                               integer_biases(comp, ib), self.yte)
        out = price(comp, ib)
        out["accuracy"] = integer_accuracy(comp, ib, self.xte, self.yte)
        return out
