"""Set-up's warm-up of the netlist engine's launch shapes.

A launch of the netlist engine specializes on the population size, the
number of node slots and the number of waves, each rounded up to a power
of two. Which of them a window reaches depends on the netlists its
candidates happen to lower to, so warm-up search jobs miss some. Here each
reachable combination is launched once through the program's public
``simulate_population``, on stand-in netlists built to its size: a chain of
``levels`` additions (one wave each) and constants up to ``nodes`` slots.
The ranges come from ``netlist_warmup``: the node count of one candidate
and the waves of one launch, as the cell's searches produce them. They are
the traffic mix's where it has them, else the configuration's.
"""
from __future__ import annotations

from typing import Dict, Iterator, Tuple

import numpy as np


def pow2_between(lo: int, hi: int) -> Iterator[int]:
    """The powers of two from the one at or above ``lo`` to the one at or
    above ``hi``."""
    b = 1 << (max(int(lo), 1) - 1).bit_length()
    while True:
        yield b
        if b >= hi:
            return
        b <<= 1


def shapes(population: int, warm: Dict, n_in: int, n_cls: int
           ) -> Iterator[Tuple[int, int, int]]:
    """(candidates, levels, nodes per candidate) for every launch bucket
    a generation of at most ``population`` candidates can reach."""
    lo, hi = warm["nodes"]
    for p in pow2_between(1, population):
        for levels in pow2_between(*warm["waves"]):
            for slots in pow2_between(p * lo + 1, p * hi + 1):
                nodes = (slots - 1) // p
                if nodes >= n_in + levels + n_cls:
                    yield p, levels, nodes


def stand_in(ir, n_in: int, n_cls: int, in_bits: int, levels: int,
             nodes: int):
    """A netlist of ``nodes`` nodes whose computational nodes form one
    chain of ``levels`` additions, so each level holds a single one."""
    net = ir.Netlist(in_bits=in_bits, w_bits=[in_bits])
    xs = [net.input(i) for i in range(n_in)]
    v = xs[0]
    for lv in range(levels):
        v = net.add(v, xs[(lv + 1) % n_in])
    logits = [v] + [net.const(c) for c in range(1, n_cls)]
    c = n_cls
    while len(net.nodes) < nodes - 1:          # the argmax takes the last
        net.const(c)
        c += 1
    net.argmax(logits)
    return net


def warm_range(config: Dict, mix: Dict) -> Dict:
    """The cell's ``netlist_warmup``: the mix's, else the configuration's."""
    return mix.get("netlist_warmup", config["netlist_warmup"])


def netlist_buckets(program, config: Dict, mix: Dict, test_rows: int) -> int:
    """Launch each bucket the cell can reach once on ``test_rows`` rows;
    -> the number of launches."""
    dims = config["layer_dims"]
    n_in, n_cls = dims[0], dims[-1]
    x = np.zeros((test_rows, n_in), np.int64)
    packed = {}
    n = 0
    for p, levels, nodes in shapes(mix["population"],
                                   warm_range(config, mix), n_in, n_cls):
        if (levels, nodes) not in packed:
            packed[(levels, nodes)] = program.NS.pack_netlist(stand_in(
                program.ir, n_in, n_cls, config["input_bits"], levels,
                nodes))
        program.NS.simulate_population(
            program.NS.pack_population([packed[(levels, nodes)]] * p), x)
        n += 1
    return n
