"""Chip smoke test: the netlist-exact GA search, end to end on one TPU.

Runs the paper pipeline through its normal entry point
(`examples/printed_mlp_minimization.main`) on pendigits, the widest schema
(16-20-10, 10,992 samples): pretrain, the batched quantization sweep, the
netlist-exact NSGA-II (population 8, 3 generations, 60 epochs), compile of
the chosen point and the approximation budget fit. The evaluation cache
starts empty, so every candidate is trained and simulated on the chip. It
then checks the device netlist engine against the NumPy oracle on up to
four distinct Pareto-front circuits.

    python chip_smoke.py

It fails (non-zero exit, no result line) without a TPU, when any candidate
was quarantined, when the engine and the oracle differ anywhere, or when
the front is empty or holds a non-finite value. The last line of a passing
run is one JSON object naming the device. The seconds it prints are chip
set-up including compilation, not a benchmark.
"""
from __future__ import annotations

import json
import math
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
DATASET = "pendigits"
EPOCHS = 60       # the example's finetune length without --full
N_ORACLE = 4      # distinct front circuits checked against the oracle


def _fail(msg: str) -> int:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr)
    return 1


def main() -> int:
    if not (ROOT / "src" / "repro").is_dir():
        return _fail(f"no repro package under {ROOT / 'src'}: run this "
                     "script from a checkout of the repository")
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

    import jax
    import numpy as np

    from repro.configs import backend
    backend.enable_compile_cache()
    try:
        devices = jax.devices()
    except RuntimeError as e:
        return _fail(f"JAX found no device: {e}")
    dev = devices[0]
    if dev.platform != "tpu":
        return _fail(f"no TPU: JAX's first device is {dev.platform} "
                     f"({dev.device_kind}); this smoke test runs only on a "
                     "TPU")
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}, "
          f"jax {jax.__version__}, netlist engine "
          f"{backend.default_netlist_engine()}")

    from examples import printed_mlp_minimization as demo
    from repro import circuit
    from repro.configs.printed_mlp import PRINTED_MLPS
    from repro.core import minimize as MZ
    from repro.core.compression_spec import ModelMin
    from repro.kernels import netlist_sim as NS
    from repro.obs import metrics as MT
    from repro.obs import xprof

    cfg = PRINTED_MLPS[DATASET]
    seconds = {}
    with xprof.count_compiles() as cc:
        with tempfile.TemporaryDirectory(prefix="chip_smoke_evals_") as d:
            t0 = time.perf_counter()
            res = demo.main(["--dataset", DATASET, "--cache-dir", d])
            seconds["pipeline"] = time.perf_counter() - t0

        # the device engine against the oracle, on the front's circuits
        t0 = time.perf_counter()
        front = res["pareto_front"]
        _, _, xte, _ = MZ.dataset_for(cfg)
        specs = list(dict.fromkeys(spec for *_, spec in front))[:N_ORACLE]
        nets, xq = [], []
        for spec_json in specs:
            net, compiled = circuit.compile_spec(
                cfg, ModelMin.from_json(spec_json), epochs=EPOCHS)
            nets.append(net)
            xq.append(MZ.quantize_inputs(compiled, xte))
        oracle_equal, operands_differing = 0, 0
        if nets:
            pop = NS.pack_population(nets)
            xq = np.stack(xq)
            got = NS.simulate_population(pop, xq)["amx"]
            want = NS.simulate_population_ref(pop, xq)["amx"]
            oracle_equal = sum(np.array_equal(got[p], want[p])
                               for p in range(len(nets)))
            operands_differing = int((got != want).sum())
        seconds["oracle"] = time.perf_counter() - t0

    counters = MT.snapshot()["counters"]
    quarantined = {k: v for k, v in counters.items()
                   if k.startswith("eval.quarantine.") and v}
    batched_acc = 1.0 - res["evaluations"][res["chosen"]][0]
    serial_acc = res["chosen_netlist_accuracy"]

    print("\n--- chip_smoke summary ---")
    for phase, s in seconds.items():
        print(f"phase {phase}: {s!r} s wall-clock "
              "(chip set-up including compile, not a benchmark)")
    print(f"backend compiles: {cc.compiles} ({cc.compile_s!r} s)")
    print(f"evaluations: {res['n_evaluations']} unique GA specs, "
          f"{counters.get('eval.specs_evaluated', 0)} specs evaluated, "
          f"quarantines: {sum(quarantined.values())}")
    print(f"oracle: {oracle_equal}/{len(nets)} front circuits equal, "
          f"{operands_differing} comparator operands differing over "
          f"{len(nets)} x {len(xte)} x {cfg.n_classes}")
    print(f"baseline accuracy: {float(res['baseline_acc'])!r}, gain at 5% "
          f"loss: {float(res['combined_gain_at_5pct'])!r}x, front size: "
          f"{len(front)}")
    print(f"serial_vs_batched_equal: {serial_acc == batched_acc} "
          f"(chosen point: netlist_accuracy {serial_acc!r}, batched "
          f"{batched_acc!r})")

    if quarantined:
        return _fail(f"quarantined candidates: {quarantined}")
    if oracle_equal != len(nets):
        return _fail(f"engine and oracle differ on "
                     f"{len(nets) - oracle_equal} of {len(nets)} circuits")
    if not front:
        return _fail("empty Pareto front")
    if not all(math.isfinite(v) for acc, area, delay, _ in front
               for v in (acc, area, delay)):
        return _fail(f"non-finite value on the Pareto front: {front}")

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
