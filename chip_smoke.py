"""Smoke run of the engine's main path on TPU chips.

Default, one chip: generate TPC-H at ``--sf`` (1) from ``--seed``, serve the
22 query templates with their default bindings through ``QueryServer`` on the
first chip, and check every answer against the NumPy reference
(``RefContext``) at rtol 1e-7.  Then serve each template again and check
that nothing recompiled.

``--chips 4``: the same through ``QueryServer(db, devices=4)`` at ``--sf``
(4): tables hash-partitioned over a four-chip mesh (paper §4.3), one SPMD
program per template.  Also checks that every program takes its inputs on
the four chips, and each template's exchanges against the plan's static
(paper Table 4) counts.

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
# a plan's static exchange counts -> QueryServer.exchanges' kinds
_KIND = {"shuffles": "shuffle", "broadcasts": "broadcast",
         "final_gathers": "gather", "allreduces": "allreduce"}


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


def serve_phase(sf: float, seed: int, chips: int) -> list[str]:
    """All 22 templates through ``QueryServer`` on ``chips``; failures."""
    import jax
    from repro.core import backend as B
    from repro.serve.server import QueryServer
    from repro.serve.templates import TEMPLATES

    devices = tpu_devices(chips)
    db = generate(sf, seed)
    server = QueryServer(db, devices=chips)
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
            on = set().union(*(sh.device_set for sh in
                               jax.tree.leaves(exe.input_shardings)))
            if on != set(devices):
                failed.append(f"{label}: runs on {len(on)} chips")
            if chips > 1:
                got = server.exchanges(template)
                static = template.query.static_counts()
                print(f"{label} exchanges={got} static={static}", flush=True)
                if any(got[_KIND[k]] != v for k, v in static.items()):
                    failed.append(f"{label}: exchanges {got} != {static}")
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
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    print(f"# peak_bytes_in_use per chip={peaks}", flush=True)
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
    failed = serve_phase(args.sf or float(args.chips), args.seed, args.chips)
    if failed:
        sys.exit(f"chip_smoke: failed: {', '.join(failed)}")
    print(json.dumps({"ok": True, "device": {
        "platform": device.platform, "kind": device.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
