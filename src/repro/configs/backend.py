"""Computation-environment configuration for multi-backend runs.

One place for the process-level JAX knobs the benchmarks, the search
runtime and the netlist-sim engines need when the repo leaves its default
CPU-pytest habitat: 64-bit lanes, platform selection (with the standard
GPU XLA flag set), host-device fan-out for pmap-style CPU runs, and NaN
debugging. All of these only take full effect at the beginning of the
program — call :func:`configure` (or the individual setters) before any
JAX computation, or drive them through the ``REPRO_*`` environment
variables it reads.

``default_netlist_engine`` is the routing policy for
`repro.kernels.netlist_sim`: the wave-scheduled ``lax.scan`` engine on
every backend. The Pallas netlist kernel does not compile for TPU yet (its
``(1, N)`` table blocks are not (8, 128)-aligned), so it runs only when
asked for, in interpret mode off-TPU. ``REPRO_NETLIST_ENGINE`` overrides.

``enable_compile_cache`` places JAX's persistent compilation cache for the
entry points (examples, benchmarks, ``chip_smoke.py``); importing ``repro``
never turns it on.
"""
from __future__ import annotations

import os
import warnings
from multiprocessing import cpu_count
from pathlib import Path

import jax


def jax_enable_x64(use_x64: bool) -> None:
    """Default integer/float width 64 bits process-wide. The netlist-sim
    engines prefer the *local* ``jax.enable_x64(True)`` scope and only need
    this for debugging sessions."""
    if not use_x64:
        use_x64 = bool(os.getenv("JAX_ENABLE_X64", 0))
    jax.config.update("jax_enable_x64", use_x64)


def set_platform(platform: str = "cpu") -> None:
    """Select 'cpu' | 'gpu' | 'tpu'. Only takes effect at the beginning of
    the program. GPU gets the standard performance flag set
    (<https://jax.readthedocs.io/en/latest/gpu_performance_tips.html>)."""
    jax.config.update("jax_platform_name", platform)
    if platform == "gpu":
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") +
            " --xla_gpu_enable_triton_softmax_fusion=true"
            " --xla_gpu_triton_gemm_any=True"
            " --xla_gpu_enable_async_collectives=true"
            " --xla_gpu_enable_latency_hiding_scheduler=true"
            " --xla_gpu_enable_highest_priority_async_stream=true"
        ).strip()


def set_cpu_cores(n: int) -> None:
    """Expose ``n`` XLA host devices (for device-parallel CPU runs).
    CPU-platform only; must run before any JAX computation."""
    n = int(n)
    total = cpu_count()
    if n > total:
        warnings.warn(f"only {total} CPUs available, will use {total - 1}",
                      Warning)
        n = total - 1
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") +
        f" --xla_force_host_platform_device_count={n}").strip()


def set_debug_nan(flag: bool) -> None:
    """Raise on the first NaN any jitted computation produces."""
    jax.config.update("jax_debug_nans", flag)


def default_netlist_engine() -> str:
    """'levels' on every backend; overridable with
    ``REPRO_NETLIST_ENGINE=levels|pallas|ref``."""
    env = os.environ.get("REPRO_NETLIST_ENGINE", "").strip().lower()
    if env in ("levels", "pallas", "ref"):
        return env
    return "levels"


# <repo root>/.jax_cache: a fixed path, because the path is part of the
# cache's key and a directory that moves between runs never hits
REPO_COMPILE_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> None:
    """Turn on JAX's persistent compilation cache for an entry point.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    this sets nothing. Otherwise the cache goes to
    ``<repo root>/.jax_cache``."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(REPO_COMPILE_CACHE))


def configure(*, platform: str | None = None, x64: bool | None = None,
              cpu_cores: int | None = None,
              debug_nan: bool | None = None) -> None:
    """Apply the requested knobs, falling back to ``REPRO_PLATFORM``,
    ``REPRO_X64``, ``REPRO_CPU_CORES`` and ``REPRO_DEBUG_NAN`` when an
    argument is None. Unset knobs are left at the JAX defaults."""
    def env(name):
        v = os.environ.get(name, "").strip()
        return v or None

    platform = platform if platform is not None else env("REPRO_PLATFORM")
    if platform:
        set_platform(platform)
    if x64 is None and env("REPRO_X64"):
        x64 = env("REPRO_X64") not in ("0", "false", "False")
    if x64 is not None:
        jax_enable_x64(bool(x64))
    cores = cpu_cores if cpu_cores is not None else env("REPRO_CPU_CORES")
    if cores:
        set_cpu_cores(int(cores))
    if debug_nan is None and env("REPRO_DEBUG_NAN"):
        debug_nan = env("REPRO_DEBUG_NAN") not in ("0", "false", "False")
    if debug_nan is not None:
        set_debug_nan(bool(debug_nan))
