"""Plain reference for a genome that carries approximation genes: the
printed circuit after its three approximation passes, simulated and
priced. Written from the papers and this repository's documented circuit
conventions; it imports nothing from `repro` and builds no netlist: each
number follows from the integer coefficients, the biases and the genes.

From the papers (Armeniakos et al., arXiv:2203.05915, cross-layer
approximation; Afentaki et al., arXiv:2312.17612):

* Truncated CSD, per layer: every non-zero integer coefficient (under
  clustering, every shared codebook product) loses its ``csd_drop``
  lowest-significance non-zero canonical-signed-digit digits; the top
  digit always stays, so no product vanishes.
* Accumulator LSB truncation, per layer: every product entering the
  layer's adder tree is floored to a multiple of 2^k, ``(p >> k) << k``;
  the bias is added after the tree.
* Argmax truncation: every logit is floored to a multiple of
  2^``argmax_lsb`` before the comparator tree; the first index wins a tie.

This repository's own conventions (``circuit/ir.py``, ``circuit/cost.py``,
``approx/passes.py`` docstrings):

* Widths come from interval arithmetic over the lowered structure. A
  product ``c * x`` is lowered as a chain over the digits of ``c``: the
  first positive digit leads (negated when every digit is negative), then
  the others in ascending order, each an add or a subtract, so a subtract
  widens the interval by the whole term. Adder trees add intervals, a
  truncation floors both ends, a ReLU clips at zero. A width is the
  two's-complement bits that hold an interval.
* A truncation by k is capped at the truncated value's width less one
  (accumulator: the product's; argmax: the logit's), and by 0 it is none.
* ReLU elision: a hidden ReLU is removed where its pre-activation's
  interval proves a sign and its worst-case approximation error is the
  point (0, 0). Errors propagate by interval arithmetic over the same
  structure: a rounded coefficient adds ``(c' - c) * x`` over the exact
  circuit's interval of its source ``x``; a truncation by k adds
  [-(2^k - 1), 0]; a ReLU gives [min(lo, 0), max(hi, 0)]. Elision is
  exact and changes only the price.
* Structural price in full-adder equivalents: a truncation is free
  wiring; each multiplier costs its digits x product width x the routing
  factor, counted once per used product (per shared codebook product
  under clustering); each tree adder costs the product width less the
  fewest zeroed low bits among the products under it (at least 1), the
  bias add the full width; a remaining ReLU costs the routing-free
  comparator at the accumulator width; the argmax tree is narrowed by the
  fewest zeroed low bits among its logits.
"""
from __future__ import annotations

import math
from typing import Dict, List, Sequence, Tuple

import numpy as np

from .printed_mlp import (AREA_FA_MM2, ARGMAX_FA_EQ, MULT_ROUTING_FACTOR,
                          POWER_FA_MW, RELU_FA_EQ)

Interval = Tuple[int, int]


def genes(spec: Dict):
    """-> (csd_drop per layer, lsb per layer, argmax_lsb), or None for a
    genome without approximation genes."""
    drop = [int(l.get("csd_drop", 0)) for l in spec["layers"]]
    lsb = [int(l.get("lsb", 0)) for l in spec["layers"]]
    am = int(spec.get("argmax_lsb", 0))
    if not any(drop) and not any(lsb) and not am:
        return None
    return drop, lsb, am


def csd_digits(c: int) -> List[Tuple[int, int]]:
    """Canonical signed digits of ``c``: [(position, sign)], ascending."""
    neg, c, pos, out = c < 0, abs(int(c)), 0, []
    while c:
        if c & 1:
            s = -1 if (c & 3) == 3 else 1
            out.append((pos, -s if neg else s))
            c -= s
        c >>= 1
        pos += 1
    return out


def truncate_csd(c: int, drop: int) -> int:
    """``c`` without its ``drop`` lowest non-zero digits; the top stays."""
    digits = csd_digits(c)
    keep = max(len(digits) - drop, 1)
    return sum(s << p for p, s in digits[len(digits) - keep:])


def product_interval(c: int, lo: int, hi: int) -> Interval:
    """Interval of ``c * x`` for ``x`` in [lo, hi], as the lowered digit
    chain derives it."""
    digits = csd_digits(c)
    lead = next((i for i, (_, s) in enumerate(digits) if s > 0), 0)
    digits = [digits[lead]] + digits[:lead] + digits[lead + 1:]
    p, s = digits[0]
    a = (lo << p, hi << p) if s > 0 else (-(hi << p), -(lo << p))
    for p, s in digits[1:]:
        t = (lo << p, hi << p)
        a = ((a[0] + t[0], a[1] + t[1]) if s > 0
             else (a[0] - t[1], a[1] - t[0]))
    return a


def width(lo: int, hi: int) -> int:
    """Two's-complement bits that hold every integer in [lo, hi]."""
    return max(hi.bit_length() + 1 if hi > 0 else 1,
               (-lo - 1).bit_length() + 1 if lo < 0 else 1)


def capped(k: int, iv: Interval) -> int:
    """A truncation by ``k`` of a value in ``iv``, capped at its width
    less one."""
    return min(k, max(width(*iv) - 1, 0)) if k > 0 else 0


def _layer(q, b_int, drop: int, lsb: int, src: Sequence[Interval],
           src_err: Sequence[Interval], src_exact: Sequence[Interval]):
    """One dense layer's approximated coefficients, per-product
    truncations and the intervals of its pre-activations: -> (q', K,
    value, error and exact-circuit intervals per neuron)."""
    d_in, d_out = q.shape
    q2 = q.copy()
    K = np.zeros(q.shape, np.int64)
    pre, err, exact = [], [], []
    for k in range(d_out):
        v, e, x = (int(b_int[k]),) * 2, (0, 0), (int(b_int[k]),) * 2
        for j in range(d_in):
            c = int(q[j, k])
            if c == 0:
                continue
            c2 = truncate_csd(c, drop)
            q2[j, k] = c2
            pv = product_interval(c2, *src[j])
            pe = product_interval(c2, *src_err[j])
            d, (xl, xh) = c2 - c, src_exact[j]
            pe = (pe[0] + min(d * xl, d * xh), pe[1] + max(d * xl, d * xh))
            K[j, k] = t = capped(lsb, pv)
            pv = ((pv[0] >> t) << t, (pv[1] >> t) << t)
            if t:
                pe = (pe[0] - ((1 << t) - 1), pe[1])
            px = product_interval(c, *src_exact[j])
            v = (v[0] + pv[0], v[1] + pv[1])
            e = (e[0] + pe[0], e[1] + pe[1])
            x = (x[0] + px[0], x[1] + px[1])
        pre.append(v)
        err.append(e)
        exact.append(x)
    return q2, K, pre, err, exact


def _relu(iv: Interval) -> Interval:
    return (max(iv[0], 0), max(iv[1], 0))


def _adder_tree_fa(tz: List[int], pw: int) -> float:
    """Balanced pairwise adder tree over operands with ``tz`` zeroed low
    bits: each adder costs ``pw`` less the fewest zeroed bits under it."""
    fa = 0.0
    while len(tz) > 1:
        nxt = [min(a, b) for a, b in zip(tz[::2], tz[1::2])]
        fa += float(sum(max(pw - t, 1) for t in nxt))
        if len(tz) % 2:
            nxt.append(tz[-1])
        tz = nxt
    return fa


def evaluate(comp, input_bits: int, g, a0: np.ndarray, b_ints, y
             ) -> Dict[str, float]:
    """Accuracy and structural price of the approximated circuit.

    ``comp``: the fixed-point compile, [(q, scale, bias, clusters or
    None, w_bits)] per layer; ``g``: `genes`; ``a0``: the ADC-quantised
    test inputs; ``b_ints``: each layer's integer bias."""
    drop, lsb, am_lsb = g
    n_layers = len(comp)
    src = [(0, (1 << input_bits) - 1)] * comp[0][0].shape[0]
    src_err = [(0, 0)] * len(src)
    src_exact = list(src)
    a = np.asarray(a0, np.int64)
    layer_fa, n_mult = [], 0
    for i, (q, _, _, clusters, bits) in enumerate(comp):
        q = np.asarray(q, np.int64)
        pw = input_bits + bits
        q2, K, pre, err, exact = _layer(q, b_ints[i], drop[i], lsb[i], src,
                                        src_err, src_exact)
        prods = (a[:, :, None] * q2[None]) >> K[None]
        a = (prods << K[None]).sum(axis=1) + np.asarray(b_ints[i], np.int64)

        # multipliers: one per used product, its digits x width x routing
        active = q != 0
        if clusters is not None:
            idx, cb = clusters
            used = {(j, int(idx[j, k])) for j, k in zip(*np.nonzero(active))}
            coeffs = [truncate_csd(int(cb[j, m]), drop[i])
                      for j, m in sorted(used)]
        else:
            coeffs = [int(c) for c in q2[active]]
        n_mult += len(coeffs)
        digits = sum(len(csd_digits(c)) for c in coeffs)
        mult_fa = float(digits * pw) * MULT_ROUTING_FACTOR
        adder_fa = 0.0
        for k in range(q.shape[1]):
            adder_fa += _adder_tree_fa(
                [int(K[j, k]) for j in range(q.shape[0]) if active[j, k]],
                pw)
            adder_fa += float(pw)                   # the bias add
        act_fa = 0.0
        if i < n_layers - 1:
            kept = sum(1 for v, e in zip(pre, err)
                       if not (e == (0, 0) and (v[0] >= 0 or v[1] <= 0)))
            ops = int(active.sum(axis=0).max(initial=0))
            acc_w = pw + math.ceil(math.log2(max(ops, 2)))
            act_fa = kept * RELU_FA_EQ * acc_w
            a = np.maximum(a, 0)
            src = [_relu(v) for v in pre]
            src_err = [(min(e[0], 0), max(e[1], 0)) for e in err]
            src_exact = [_relu(x) for x in exact]
        layer_fa.append(mult_fa + adder_fa + act_fa)

    ks = np.array([capped(am_lsb, v) for v in pre], np.int64)
    logits = (a >> ks[None]) << ks[None]
    am_w = max(input_bits + comp[-1][4] + 4 - int(ks.min()), 1)
    argmax_fa = max(len(pre) - 1, 0) * ARGMAX_FA_EQ * am_w
    total = sum(layer_fa) + argmax_fa
    return {"area_mm2": total * AREA_FA_MM2, "power_mw": total * POWER_FA_MW,
            "n_multipliers": n_mult,
            "accuracy": float(np.mean(np.argmax(logits, axis=1)
                                      == np.asarray(y)))}
