"""Shared benchmark utilities: timing, CSV emission, subprocess launcher."""
from __future__ import annotations

import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def emit(name: str, us_per_call: float, derived: str = ""):
    print(f"{name},{us_per_call:.1f},{derived}", flush=True)


def time_fn(fn, *args, warmup: int = 2, iters: int = 5) -> float:
    """Median wall seconds per call (jit-warmed)."""
    import jax
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    ts.sort()
    return ts[len(ts) // 2]


def run_subprocess_bench(module: str, n_devices: int = 8, timeout: int = 1800,
                         extra_env: dict | None = None) -> str:
    """Run ``python -m benchmarks.<module>`` on N virtual CPU devices.

    The child is pinned to ``JAX_PLATFORMS=cpu``: a parent that has touched
    JAX holds the accelerator, so a child asking for it would fail or hang.
    A failed child raises ``RuntimeError`` with the end of its stderr."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env.update(extra_env or {})
    r = subprocess.run([sys.executable, "-m", f"benchmarks.{module}"],
                       env=env, capture_output=True, text=True,
                       timeout=timeout, cwd=ROOT)
    if r.returncode != 0:
        raise RuntimeError(f"{module} failed (exit {r.returncode}):\n"
                           f"{r.stderr[-2000:]}")
    return r.stdout
