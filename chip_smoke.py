"""Smoke run of the engine's main path on TPU chips.

Default, one chip: generate TPC-H at ``--sf`` (1) from ``--seed``, serve the
22 query templates with their default bindings through ``QueryServer`` on the
first chip, and check every answer against the NumPy reference
(``RefContext``) at rtol 1e-7.  Then serve each template again and check
that nothing recompiled.

``--chips 4``: run the 22 queries SPMD through ``QueryRunner`` on a
four-chip mesh at ``--sf`` (4), tables hash-partitioned over the chips
(paper §4.3).  Checks the answers, each query's shuffle/broadcast counts
against the plan's static (paper Table 4) counts, and that every partitioned
input sits on all four chips.

Per-query lines are timings of this one run, not a benchmark: compile
seconds (the 22 programs compile at once on a thread pool, so each number
includes waiting for the host's cores), first and warm wall clock in ms,
rows, attempts, and how many Pallas kernels (``tpu_custom_call``) the
compiled executable holds.  The last line
is one JSON object, ``{"ok": true, "device": {...}}``, printed only when
every check passed; with no TPU, or after any failure, the script exits
non-zero without it.

    python chip_smoke.py [--sf 1] [--seed 0]
    python chip_smoke.py --chips 4 [--sf 4]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from concurrent.futures import ThreadPoolExecutor, wait

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

RTOL = 1e-7


def compare(got: dict, want: dict, label: str) -> None:
    """The query tests' comparison: common columns, row counts, rtol 1e-7."""
    keys = set(got) & set(want)
    assert keys, f"{label}: no common output columns"
    n = len(next(iter(want.values())))
    for k in sorted(keys):
        assert len(got[k]) == n, \
            f"{label} {k}: {len(got[k])} rows, reference has {n}"
        np.testing.assert_allclose(np.asarray(got[k], dtype=np.float64),
                                   np.asarray(want[k], dtype=np.float64),
                                   rtol=RTOL, err_msg=f"{label} {k}")


def tpu_devices(count: int) -> list:
    """The first ``count`` TPU devices; exits when JAX sees no TPU."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU, JAX sees {devices[0].platform}")
    if len(devices) < count:
        sys.exit(f"chip_smoke: {count} chips asked, JAX sees {len(devices)}")
    return devices[:count]


def generate(sf: float, seed: int):
    from repro.data import tpch
    t0 = time.perf_counter()
    db = tpch.generate(sf, seed=seed)
    nbytes = sum(np.asarray(c).nbytes
                 for t in db.tables.values() for c in t.values())
    print(f"# TPC-H sf={sf} seed={seed}: {nbytes / 1e6:.0f} MB of columns, "
          f"generated in {time.perf_counter() - t0:.1f} s", flush=True)
    return db


def _timed(fn, *args):
    t0 = time.perf_counter()
    out = fn(*args)
    return time.perf_counter() - t0, out


def serve_phase(sf: float, seed: int) -> list[str]:
    """All 22 templates through ``QueryServer`` on one chip; failures."""
    from repro.core import backend as B
    from repro.serve.server import QueryServer
    from repro.serve.templates import TEMPLATES

    device = tpu_devices(1)[0]
    db = generate(sf, seed)
    server = QueryServer(db)
    templates = dict(sorted(TEMPLATES.items()))
    # XLA releases the GIL while it compiles, and a v5e program with large
    # sorts takes tens of seconds to compile: compile all 22 at once, and
    # compute the NumPy reference answers on the host meanwhile
    threads = min(len(templates), os.cpu_count() or 1)
    with ThreadPoolExecutor(threads) as pool:
        t0 = time.perf_counter()
        compiling = {qid: pool.submit(_timed, server.compiled, t)
                     for qid, t in templates.items()}
        want = {qid: pool.submit(B.run_reference, t.bind(), db)
                for qid, t in templates.items()}
        wait(compiling.values())
    print(f"# compiled {len(templates)} programs in "
          f"{time.perf_counter() - t0:.1f} s on {threads} threads", flush=True)
    failed, rows = [], {}
    for qid, template in templates.items():
        label = f"q{qid:02d}"
        try:
            compile_s, exe = compiling[qid].result()
            reruns = server.overflow_reruns
            first_s, got = _timed(server.submit, template)
            attempts = 1 + server.overflow_reruns - reruns
            compare(got, want[qid].result()[0], label)
            rows[qid] = len(next(iter(got.values())))
            print(f"{label} compile_s={compile_s:.1f} "
                  f"first_ms={first_s * 1e3:.1f} rows={rows[qid]} "
                  f"attempts={attempts} "
                  f"tpu_custom_calls={exe.as_text().count('tpu_custom_call')}"
                  f" match", flush=True)
            if attempts != 1:
                failed.append(f"{label}: {attempts} attempts")
        except Exception as e:  # report every query, then fail the run
            print(f"{label} FAILED {type(e).__name__}: {e}", flush=True)
            failed.append(label)
    recompiles = server.recompiles
    for qid, template in templates.items():
        if qid in rows:
            warm_s, got = _timed(server.submit, template)
            print(f"q{qid:02d} warm_ms={warm_s * 1e3:.1f} "
                  f"rows={len(next(iter(got.values())))}", flush=True)
    print(f"# second pass: {server.recompiles - recompiles} recompiles",
          flush=True)
    if server.recompiles != recompiles:
        failed.append("recompiled on the second pass")
    stats = device.memory_stats() or {}
    print(f"# peak_bytes_in_use={stats.get('peak_bytes_in_use')}", flush=True)
    return failed


def mesh_phase(sf: float, seed: int, chips: int) -> list[str]:
    """All 22 queries SPMD through ``QueryRunner`` on ``chips``; failures."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core import backend as B
    from repro.core.compat import make_mesh
    from repro.distributed.fault import QueryRunner
    from repro.queries import QUERIES

    devices = tpu_devices(chips)
    mesh = make_mesh((chips,), ("data",), devices=devices)
    db = generate(sf, seed)
    failed = []
    sharded, _ = B.partition_database(db, chips)
    for name, cols in B.place_partitions(sharded, mesh).items():
        for col, arr in cols.items():
            on = {s.device for s in arr.addressable_shards}
            if on != set(devices) or arr.sharding.device_set != set(devices):
                failed.append(f"{name}.{col} sits on {sorted(map(str, on))}")
    print(f"# partitioned inputs on {chips} devices: "
          f"{'yes' if not failed else 'NO'}", flush=True)
    # QueryRunner builds a fresh program per call: compile all 22 at once
    # into the persistent compilation cache, which its compiles then read
    spec = NamedSharding(mesh, P("data"))
    shapes = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=spec),
        sharded)
    queries = dict(sorted(QUERIES.items()))
    threads = min(len(queries), os.cpu_count() or 1)
    with ThreadPoolExecutor(threads) as pool:
        t0 = time.perf_counter()
        compiling = {
            qid: pool.submit(_timed, lambda q: B.distributed_program(
                q, db, mesh)[0].lower(shapes).compile(), q)
            for qid, q in queries.items()}
        want = {qid: pool.submit(B.run_reference, q, db)
                for qid, q in queries.items()}
        wait(compiling.values())
    print(f"# compiled {len(queries)} programs in "
          f"{time.perf_counter() - t0:.1f} s on {threads} threads", flush=True)
    runner = QueryRunner(db, mesh)
    for qid, query in queries.items():
        label = f"q{qid:02d}"
        try:
            compile_s, exe = compiling[qid].result()
            res = runner.run(query)
            compare(res.result, want[qid].result()[0], label)
            counts, static = res.stats.counts(), query.static_counts()
            print(f"{label} compile_s={compile_s:.1f} "
                  f"run_ms={res.wall_s * 1e3:.1f} "
                  f"rows={len(next(iter(res.result.values())))} "
                  f"attempts={res.attempts} shuffles={counts['shuffles']} "
                  f"broadcasts={counts['broadcasts']} "
                  f"tpu_custom_calls={exe.as_text().count('tpu_custom_call')}"
                  f" match", flush=True)
            if counts != static:
                failed.append(f"{label}: counts {counts} != static {static}")
            if res.attempts != 1:
                failed.append(f"{label}: {res.attempts} attempts")
        except Exception as e:  # report every query, then fail the run
            print(f"{label} FAILED {type(e).__name__}: {e}", flush=True)
            failed.append(label)
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    print(f"# peak_bytes_in_use per device={peaks}", flush=True)
    return failed


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--sf", type=float, default=None,
                    help="TPC-H scale factor (default 1, or 4 with --chips 4)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    from repro import use_compile_cache

    device = tpu_devices(args.chips)[0]
    print(f"# device platform={device.platform} kind={device.device_kind} "
          f"count={len(jax.devices())}  compile cache={use_compile_cache()}",
          flush=True)
    if args.chips == 1:
        failed = serve_phase(args.sf or 1.0, args.seed)
    else:
        failed = mesh_phase(args.sf or 4.0, args.seed, args.chips)
    if failed:
        sys.exit(f"chip_smoke: failed: {', '.join(failed)}")
    print(json.dumps({"ok": True, "device": {
        "platform": device.platform, "kind": device.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
