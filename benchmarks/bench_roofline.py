"""§Roofline source: per-(arch x shape x mesh) terms from the dry-run JSONs.

Run ``python -m repro.launch.dryrun --all`` first; this module reduces the
records into the roofline table (also embedded in EXPERIMENTS.md).
"""
from __future__ import annotations

import glob
import json
import os

from .common import ROOT, emit

RESULTS = os.path.join(ROOT, "results", "dryrun")


def main():
    files = sorted(glob.glob(os.path.join(RESULTS, "*.json")))
    if not files:
        emit("roofline_missing", 0, "run: python -m repro.launch.dryrun --all")
        return
    for f in files:
        r = json.load(open(f))
        name = f"roofline_{r['arch']}_{r['shape']}_{r['mesh']}"
        if r.get("skipped"):
            emit(name, 0, f"skipped:{r['skipped'][:40]}")
            continue
        if not r.get("ok"):
            emit(name, 0, f"FAILED:{r.get('error', '')[:60]}")
            continue
        rf = r["roofline"]
        emit(name, rf["step_lower_bound_s"] * 1e6,
             f"bottleneck={rf['bottleneck']};"
             f"compute_ms={rf['compute_s'] * 1e3:.2f};"
             f"memory_ms={rf['memory_s'] * 1e3:.2f};"
             f"collective_ms={rf['collective_s'] * 1e3:.2f};"
             f"roofline_frac={rf.get('roofline_frac', 0):.4f};"
             f"useful_flops={rf.get('useful_flop_frac', 0):.3f}")


if __name__ == "__main__":
    from repro import use_compile_cache
    use_compile_cache()
    main()
