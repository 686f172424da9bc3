"""One run of one cell: set-up, the window (or one traced pass), the check.

``run_cell`` returns the result line as a dict.  Everything is made from the
seed: the tables, and the parameters of every request, the warm pass's and
the window's from streams of their own.
"""
from __future__ import annotations

import dataclasses
import gc
import json
import os
import sys
import tempfile
import time

from . import check, datagen, device, trace as tr
from .client import Client, CompileCounter, Execution, GcPauses
from .spec import Bench
from .traffic import WARM_STREAM, WINDOW_STREAM


@dataclasses.dataclass
class Run:
    """What a metric's reader reads."""
    executions: list[Execution]
    window_s: float
    setup_s: float
    peaks: dict | None
    column_bytes: dict[str, int]
    query_columns: dict[int, list[str]]
    chips: int
    trace: tr.Summary | None = None


def configure_jax(root: str) -> str:
    """The persistent compilation cache at the fixed ``<checkout>/.jax_cache``
    (which ``repro.use_compile_cache`` also finds), every program in it."""
    import jax
    path = os.path.join(root, ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    sys.path.insert(0, os.path.join(root, "src"))
    import repro
    repro.use_compile_cache()
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def log(msg: str) -> None:
    print(f"# {msg}", flush=True)


def run_cell(root: str, workload: str, seed: int, seconds: float,
             traced: bool, t_start: float) -> dict:
    cell = Bench(root).cell(workload)
    configure_jax(root)
    import jax
    devices, peaks = device.chips(jax.devices(), cell.chips,
                                  device.load_peaks())
    log(f"device platform={devices[0].platform} "
        f"kind={devices[0].device_kind} count={len(devices)}")

    conf = cell.config
    data = datagen.generate(conf["scale_factor"], seed, skew=conf["skew"],
                            hot_share=conf["hot_share"])
    with CompileCounter() as setup:
        client = Client(data, devices)
        client.prepare(cell.traffic.queries)
        warm = client.run_pass(next(cell.traffic.passes(seed, WARM_STREAM)))
    for ex in warm:
        if ex.error:
            raise RuntimeError(f"warm pass: q{ex.qid} {ex.params}: "
                               f"{ex.error}")
    log(f"setup: {setup.obtained} programs, {setup.compiled} compiled, "
        f"{setup.cache_hits} read from the compilation cache"
        + (f" (compiled: {', '.join(setup.compiled_names)})"
           if setup.compiled_names else ""))
    setup_s = time.time() - t_start

    passes = cell.traffic.passes(seed, WINDOW_STREAM)
    before = client.counters()
    summary = None
    with tempfile.TemporaryDirectory() as tmp:
        with CompileCounter() as during, GcPauses() as gcs:
            if traced:
                with tr.capture(tmp):
                    t0 = time.perf_counter()
                    executions = client.run_pass(next(passes))
                    window_s = time.perf_counter() - t0
            else:
                executions, window_s = client.window(passes, seconds)
        after = client.counters()
        if traced:
            summary = tr.load(tmp, tr.programs(client.server,
                                               cell.traffic.queries))
    log("window: " + " ".join(
        [f"{len(executions)} requests in {window_s:.3f} s,",
         f"{during.compiled} compiled,", f"{during.cache_hits} read,"]
        + [f"{k} {after[k] - before[k]}" for k in sorted(after)]
        + [f"gc {len(gcs.pauses)} collections, longest "
           f"{1e3 * max(gcs.pauses, default=0.0):.1f} ms"]))
    by: dict[int, list[float]] = {}
    for ex in executions:
        by.setdefault(ex.qid, []).append(1e3 * ex.latency_s)
    log("latency ms: " + ", ".join(
        f"q{q} {min(v):.1f}-{max(v):.1f} (x{len(v)})" for q, v in by.items()))
    dev = device.describe(devices)
    del client, warm                 # free the program's state
    gc.collect()

    t0 = time.perf_counter()
    numbers, where = check.check(executions, data, conf["reference"],
                                 cell.traffic.limits)
    log(f"check: {len(executions)} answers against the reference in "
        f"{time.perf_counter() - t0:.1f} s; largest relative error in "
        f"{where or '-'}")
    failed = sum(ex.error is not None for ex in executions)
    with open(os.path.join(root, "bench", "querybytes.json")) as f:
        query_columns = {int(q): v["columns"]
                         for q, v in json.load(f)["queries"].items()}
    run = Run(executions, window_s, setup_s, peaks,
              data.column_bytes(), query_columns, len(devices), summary)
    metrics = {}
    for m in cell.metrics:
        if (m.kind == "per_layer") != traced:
            continue
        value = m.read(run)
        if value is not None:
            metrics[m.name] = {"value": value, "unit": m.unit}
    result = {"correct": check.passed(numbers) and failed == 0,
              "attempted": len(executions), "failed": failed,
              "metrics": metrics, "device": dev}
    if summary is not None:
        result["device"]["busy_s"] = summary.busy_s()
        result["device"]["window_s"] = summary.window_s
        result["breakdown"] = summary.breakdown()
    result["check"] = numbers
    return result
