import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^^ MUST precede every other import (jax locks device count on first init).

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell:
  1. FULL compile (layers under lax.scan) on the production mesh — proves the
     sharding config is coherent end-to-end; records memory_analysis().
  2. Depth-reduced UNROLLED lowers (repeats=1 and 1+e_i per depth knob) to fit
     the affine cost model (see repro.roofline.analysis) — XLA cost_analysis
     counts while bodies once, so full-depth FLOPs/bytes/collective-bytes are
     extrapolated exactly from the unrolled variants.
  3. Writes artifacts/dryrun/<arch>__<shape>__<mesh>.json (existing files are
     skipped -> the sweep is resumable / fault tolerant).

Usage:
  python -m repro.launch.dryrun --arch qwen3-0.6b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all [--mesh both] [--force]
"""
import argparse
import dataclasses
import json
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCHS, SHAPES, shape_applicable
from repro.configs.base import ArchConfig, Segment, ShapeConfig
from repro.launch import specs as SP
from repro.launch.mesh import make_production_mesh
from repro.nn import transformer as T
from repro.roofline import analysis as RA
from repro.roofline.hw import TPU_V5E
from repro.train import train_state as TS
from repro.train.optimizer import AdamWConfig

ART = Path(__file__).resolve().parents[3] / "artifacts" / "dryrun"

# ---------------------------------------------------------------------------
# §Perf variants: named config/serving transforms for the hillclimb cells.
# Each entry: (cfg_transform, serve_weight_bits, kv_cache_dtype)
# ---------------------------------------------------------------------------

VARIANTS = {
    "baseline": (lambda c: c, None, None),
    # bf16 attention scores/probs (halves the dominant HBM score bytes)
    "lowp_attn": (lambda c: dataclasses.replace(c, attn_lowp_probs=True),
                  None, None),
    # save matmul outputs under remat (trade memory for recompute bytes)
    "remat_dots": (lambda c: dataclasses.replace(c, remat_policy="dots"),
                   None, None),
    "lowp_dots": (lambda c: dataclasses.replace(
        c, attn_lowp_probs=True, remat_policy="dots"), None, None),
    # EP-local MoE routing (kills the global token-gather collectives)
    "moe_ps": (lambda c: dataclasses.replace(
        c, moe=dataclasses.replace(c.moe, dispatch="per_sample")), None, None),
    "moe_ps_lowp": (lambda c: dataclasses.replace(
        c, attn_lowp_probs=True,
        moe=dataclasses.replace(c.moe, dispatch="per_sample")), None, None),
    # paper technique on the serving path: intN weights (+ fp8 KV cache)
    "w8": (lambda c: c, 8, None),
    "w4": (lambda c: c, 4, None),
    "w8kv8": (lambda c: c, 8, "float8_e4m3fn"),
    "w4kv8": (lambda c: c, 4, "float8_e4m3fn"),
    # TP-only serving: quantized weights small enough to drop FSDP entirely
    # -> the per-layer weight all-gather disappears (XLA dequantizes shards
    # locally, so sharded intN never shrinks the gather — removing it does)
    "w8tp": (lambda c: c, 8, "float8_e4m3fn"),
    "w4tp": (lambda c: c, 4, "float8_e4m3fn"),
}

NO_FSDP_VARIANTS = {"w8tp", "w4tp"}


# ---------------------------------------------------------------------------
# depth knobs
# ---------------------------------------------------------------------------


def depth_knobs(cfg: ArchConfig):
    """Repeat counts the affine cost model fits over: one per segment, plus
    the encoder stack if present."""
    knobs = [seg.repeats for seg in cfg.segments]
    if cfg.encoder is not None:
        knobs.append(cfg.encoder.num_layers)
    return knobs


def with_depth(cfg: ArchConfig, repeats) -> ArchConfig:
    n_seg = len(cfg.segments)
    segs = tuple(Segment(s.pattern, int(r))
                 for s, r in zip(cfg.segments, repeats[:n_seg]))
    kw = {"segments": segs}
    if cfg.encoder is not None:
        kw["encoder"] = dataclasses.replace(cfg.encoder,
                                            num_layers=int(repeats[n_seg]))
    return dataclasses.replace(cfg, **kw)


# ---------------------------------------------------------------------------
# lowering one variant
# ---------------------------------------------------------------------------


def lower_cell(cfg: ArchConfig, shape: ShapeConfig, mesh, *, unroll: bool,
               serve_bits=None, kv_dtype=None, fsdp: bool = True):
    """Returns the lowered computation for one cell/variant."""
    opt_cfg = AdamWConfig()
    in_specs = SP.input_specs(cfg, shape)
    in_shard = SP.input_shardings(cfg, shape, mesh)

    # all shardings below are explicit NamedShardings; the mesh context
    # only scopes spec resolution
    with jax.set_mesh(mesh):
        if shape.kind == "train":
            state_shapes = SP.abstract_train_state(cfg)
            state_shard = SP.train_state_shardings(cfg, mesh, state_shapes)
            step = TS.make_train_step(cfg, opt_cfg, remat=True, unroll=unroll)
            jf = jax.jit(step, in_shardings=(state_shard, in_shard),
                         out_shardings=(state_shard, None),
                         donate_argnums=(0,))
            lowered = jf.lower(state_shapes, in_specs)
        elif shape.kind == "prefill":
            params_shapes = SP.abstract_params(cfg)
            pshard = SP.param_shardings(cfg, mesh, params_shapes)
            step = TS.make_prefill_step(cfg, unroll=unroll)
            jf = jax.jit(step, in_shardings=(pshard, in_shard))
            lowered = jf.lower(params_shapes, in_specs)
        else:  # decode
            from repro.serve import quantized as QS
            params_shapes = SP.abstract_params(cfg)
            dstate = SP.abstract_decode_state(cfg, shape, kv_dtype=kv_dtype)
            dshard = SP.decode_state_shardings(cfg, shape, mesh, dstate)
            if serve_bits:
                pshard, params_shapes = QS.quantized_shardings(
                    cfg, mesh, params_shapes, bits=serve_bits, fsdp=fsdp)
                step = QS.make_quant_serve_step(cfg, unroll=unroll)
            else:
                pshard = SP.param_shardings(cfg, mesh, params_shapes)
                step = TS.make_serve_step(cfg, unroll=unroll)
            jf = jax.jit(step, in_shardings=(pshard, dshard,
                                             in_shard["tokens"]),
                         out_shardings=(None, dshard),
                         donate_argnums=(1,))
            lowered = jf.lower(params_shapes, dstate, in_specs["tokens"])
    return lowered


def measure_variant(cfg, shape, mesh, repeats, *, serve_bits=None,
                    kv_dtype=None, fsdp=True) -> dict:
    from repro.nn import attention as ATT
    v = with_depth(cfg, repeats)
    ATT.CHUNK_OVERRIDE = 1 << 30   # exact-count dense attention (see module)
    try:
        lowered = lower_cell(v, shape, mesh, unroll=True,
                             serve_bits=serve_bits, kv_dtype=kv_dtype,
                             fsdp=fsdp)
        compiled = lowered.compile()
    finally:
        ATT.CHUNK_OVERRIDE = None
    out = RA.cost_dict(compiled)
    out.update({f"coll_{k}": val for k, val in
                RA.collective_bytes(compiled.as_text()).items()})
    return out


# ---------------------------------------------------------------------------
# per-cell driver
# ---------------------------------------------------------------------------


def run_cell(arch: str, shape_name: str, mesh_name: str, *,
             skip_reduced: bool = False, variant: str = "baseline") -> dict:
    cfg = ARCHS[arch]
    shape = SHAPES[shape_name]
    transform, serve_bits, kv_dtype = VARIANTS[variant]
    fsdp = variant not in NO_FSDP_VARIANTS
    if shape.kind != "decode":
        serve_bits, kv_dtype = None, None
    cfg = transform(cfg) if (cfg.moe is not None or
                             not variant.startswith("moe")) else cfg
    ok, why = shape_applicable(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                "status": "skipped", "reason": why}
    mesh = make_production_mesh(multi_pod=(mesh_name == "multi"))
    chips = int(np.prod(list(mesh.shape.values())))
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
           "chips": chips, "status": "ok", "variant": variant}

    # 1. full compile (scan) — the coherence proof + memory analysis
    t0 = time.time()
    lowered = lower_cell(cfg, shape, mesh, unroll=False,
                         serve_bits=serve_bits, kv_dtype=kv_dtype, fsdp=fsdp)
    rec["lower_s"] = round(time.time() - t0, 2)
    t0 = time.time()
    compiled = lowered.compile()
    rec["compile_s"] = round(time.time() - t0, 2)
    rec["memory"] = RA.memory_dict(compiled)
    rec["cost_raw"] = RA.cost_dict(compiled)   # body-once; see roofline note
    rec["coll_raw"] = RA.collective_bytes(compiled.as_text())
    del compiled, lowered

    # 2. depth-reduced unrolled lowers -> affine fit -> full-depth roofline
    if not skip_reduced:
        knobs = depth_knobs(cfg)
        fit = RA.fit_depth(
            lambda r: measure_variant(cfg, shape, mesh, r,
                                      serve_bits=serve_bits,
                                      kv_dtype=kv_dtype, fsdp=fsdp),
            len(knobs))
        full = fit.at(knobs)
        coll = full.get("coll_total", 0.0)
        roof = RA.Roofline(flops_per_chip=full["flops"],
                           bytes_per_chip=full["bytes"],
                           coll_bytes_per_chip=coll)
        rec["fit"] = {"base": fit.base,
                      "bodies": fit.bodies, "knobs": knobs}
        rec["roofline"] = roof.as_dict()

        # MODEL_FLOPS ratio (useful-compute fraction)
        params_shapes = SP.abstract_params(cfg)
        n_active = T.active_param_count(params_shapes, cfg)
        tokens = shape.global_batch * (shape.seq_len
                                       if shape.kind != "decode" else 1)
        mf = RA.model_flops(n_active, tokens,
                            "train" if shape.kind == "train" else "serve")
        rec["model_flops"] = mf
        rec["n_active_params"] = n_active
        hlo_global = full["flops"] * chips
        rec["useful_flops_ratio"] = mf / hlo_global if hlo_global else 0.0
    return rec


def cells(mesh_names):
    for arch in ARCHS:
        for shape_name in SHAPES:
            for mesh_name in mesh_names:
                yield arch, shape_name, mesh_name


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single", choices=["single", "multi",
                                                         "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--skip-reduced", action="store_true",
                    help="full compile only (no roofline extrapolation)")
    ap.add_argument("--variant", default="baseline", choices=sorted(VARIANTS))
    ap.add_argument("--out", default=str(ART))
    args = ap.parse_args()

    out_dir = Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)
    mesh_names = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    if args.all:
        todo = list(cells(mesh_names))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        todo = [(args.arch, args.shape, m) for m in mesh_names]

    failures = 0
    for arch, shape_name, mesh_name in todo:
        # roofline extrapolation only needed on the single-pod mesh
        skip_reduced = args.skip_reduced or (mesh_name == "multi")
        suffix = "" if args.variant == "baseline" else f"__{args.variant}"
        path = out_dir / f"{arch}__{shape_name}__{mesh_name}{suffix}.json"
        if path.exists() and not args.force:
            print(f"[skip-existing] {path.name}")
            continue
        t0 = time.time()
        try:
            rec = run_cell(arch, shape_name, mesh_name,
                           skip_reduced=skip_reduced, variant=args.variant)
        except Exception as e:  # record the failure, keep sweeping
            rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                   "status": "error", "error": f"{type(e).__name__}: {e}",
                   "traceback": traceback.format_exc()[-2000:]}
            failures += 1
        rec["wall_s"] = round(time.time() - t0, 2)
        path.write_text(json.dumps(rec, indent=2))
        status = rec["status"]
        extra = ""
        if status == "ok" and "roofline" in rec:
            r = rec["roofline"]
            extra = (f" dom={r['dominant']} t_step={r['t_step_s']:.4g}s "
                     f"useful={rec['useful_flops_ratio']:.2f}")
        print(f"[{status}] {arch} x {shape_name} x {mesh_name} "
              f"({rec['wall_s']}s){extra}", flush=True)
    print(f"done; failures={failures}")
    return failures


if __name__ == "__main__":
    raise SystemExit(main())
