"""The general traffic generator: a closed loop of whole NSGA-II search
jobs through the program's normal search entry.

Each job is ``core.ga.run_nsga2(..., batch_evaluate=core.batch_eval.
make_batch_evaluator(cfg, epochs, seed, cache=None, netlist=...))`` with
the mix's gene choices and warm-start specs, and a GA seed of its own.
A generation is one evaluation of the initial population, or one
``on_generation`` step after it. The window opens at a job's start and
closes at the first generation boundary after ``seconds``, or after a
fixed number of generations where one is given (the readings of
`bench/calibrate.py` and the CPU tests, whose work then does not depend
on the machine's speed).
"""
from __future__ import annotations

import contextlib
import dataclasses
import itertools
import time
from typing import Callable, Dict, List, Optional

import numpy as np

# SeedSequence streams under the run's --seed
STREAM_WARMUP, STREAM_CHECK, STREAM_JOB0 = 1, 2, 16


def derive(seed: int, stream: int) -> int:
    """A 31-bit seed for one purpose, from the run's --seed (any integer)."""
    ss = np.random.SeedSequence([int(seed) % (1 << 64), stream])
    return int(ss.generate_state(1, np.uint32)[0] >> 1)


class WindowClosed(Exception):
    """Raised at the first generation boundary after the window's length."""


@dataclasses.dataclass
class Batch:
    """One call of the evaluator inside the window."""
    n: int                  # candidates evaluated (the GA's unique specs)
    t0: float               # perf_counter at entry and exit
    t1: float


@dataclasses.dataclass
class Window:
    """What the window did, on the host clock."""
    seconds: float
    max_generations: Optional[int] = None   # closes it sooner, if set
    t0: float = 0.0
    t1: float = 0.0
    open: bool = False
    generations: int = 0
    jobs_started: int = 0
    batches: List[Batch] = dataclasses.field(default_factory=list)
    record: Dict = dataclasses.field(default_factory=dict)
    quarantine: List = dataclasses.field(default_factory=list)

    @property
    def elapsed(self) -> float:
        return self.t1 - self.t0

    @property
    def evaluations(self) -> int:
        return sum(b.n for b in self.batches)


def _annotation(name: str, on: bool):
    if not on:
        return contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation(name)


class Search:
    """The cell's search jobs over one configuration and mix."""

    def __init__(self, program, cfg, mix: Dict, data_seed: int):
        self.P = program                    # bench.harness.program.Program
        self.cfg = cfg
        self.mix = mix
        self.data_seed = data_seed
        self.n_layers = len(cfg.layer_dims) - 1
        ib = cfg.input_bits
        self.seed_specs = [
            program.ModelMin.uniform(self.n_layers, input_bits=ib, **s)
            for s in mix["seed_specs"]]

    def ga_config(self, ga_seed: int, population: Optional[int] = None):
        g = self.mix["genes"]
        return self.P.GAConfig(
            population=population or self.mix["population"],
            generations=self.mix["generations"], seed=ga_seed,
            input_bits=self.cfg.input_bits,
            bits_choices=tuple(g["bits"]),
            sparsity_choices=tuple(g["sparsity"]),
            cluster_choices=tuple(g["clusters"]),
            csd_drop_choices=tuple(g["csd_drop"]),
            lsb_choices=tuple(g["lsb"]),
            argmax_lsb_choices=tuple(g["argmax_lsb"]))

    def evaluator(self, window: Window):
        return self.P.BE.make_batch_evaluator(
            self.cfg, epochs=self.mix["epochs"], seed=self.data_seed,
            cache=None, netlist=self.mix["objective"] == "netlist",
            include_delay=bool(self.mix["include_delay"]),
            record=window.record, quarantine=window.quarantine)

    def evaluate(self, specs, window: Window):
        """One direct population evaluation (set-up: the finetune's
        population buckets)."""
        return self.P.BE.evaluate_population(
            self.cfg, specs, epochs=self.mix["epochs"], seed=self.data_seed,
            cache=None, netlist=self.mix["objective"] == "netlist",
            quarantine=window.quarantine)

    def random_specs(self, n: int, ga_seed: int):
        """``n`` genomes as the GA draws its initial population."""
        return self.P.init_ga_state(self.n_layers,
                                    self.ga_config(ga_seed, n)).population

    def run(self, window: Window, job_seeds: Callable[[int], int], *,
            trace: bool = False, start: bool = True,
            max_jobs: Optional[int] = None) -> Window:
        """Run jobs back to back. With ``start`` the window opens at the
        first job and closes after ``window.seconds``; without, the jobs
        run whole (``max_jobs`` of them) and nothing is measured."""
        gen_note = [None]

        def boundary():
            if not window.open:
                return
            now = time.perf_counter()
            window.generations += 1
            if gen_note[0] is not None:
                gen_note[0].__exit__(None, None, None)
                gen_note[0] = None
            if now - window.t0 >= window.seconds or \
                    window.generations == window.max_generations:
                window.t1 = now
                window.open = False
                raise WindowClosed
            if trace:
                gen_note[0] = _annotation("bench.generation", True)
                gen_note[0].__enter__()

        if start:
            window.t0 = time.perf_counter()
            window.open = True
            if trace:
                gen_note[0] = _annotation("bench.generation", True)
                gen_note[0].__enter__()
        try:
            for j in itertools.count():
                if max_jobs is not None and j >= max_jobs:
                    break
                window.jobs_started += start
                be = self.evaluator(window)
                first = [True]

                def batch(specs, be=be, first=first):
                    t0 = time.perf_counter()
                    with _annotation("bench.evaluate", trace):
                        out = be(specs)
                    if window.open:
                        window.batches.append(
                            Batch(len(specs), t0, time.perf_counter()))
                    if first[0]:
                        first[0] = False
                        boundary()
                    return out

                self.P.run_nsga2(
                    self.n_layers, None, self.ga_config(job_seeds(j)),
                    seed_specs=list(self.seed_specs), batch_evaluate=batch,
                    on_generation=lambda state: boundary())
        except WindowClosed:
            pass
        finally:
            if gen_note[0] is not None:
                gen_note[0].__exit__(None, None, None)
        return window
