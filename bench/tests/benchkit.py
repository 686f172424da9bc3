"""Shared helpers of the benchmark's tests: paths, a checkout copy with a
cell added as files, and CPU rehearsal runs in processes of their own."""
import json
import os
import shutil
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(ROOT, "src"))


def add_tiny_cell(root: str, queries=(6, 3)) -> str:
    """Add a cell at sf 0.01 to the checkout at ``root`` the way a later
    change would: a configuration file, a traffic file and two entries."""
    with open(os.path.join(BENCH, "configs", "tpch_sf1.json")) as f:
        conf = json.load(f)
    conf.update(name="tiny", scale_factor=0.01)
    with open(os.path.join(root, "bench", "configs", "tiny.json"), "w") as f:
        json.dump(conf, f)
    rules = {}
    for name in ("join", "scan_agg"):
        with open(os.path.join(BENCH, "traffic", f"{name}.json")) as f:
            mix = json.load(f)
        rules.update(mix["bindings"])
    traffic = {"queries": list(queries),
               "bindings": {str(q): rules[str(q)] for q in queries
                            if str(q) in rules},
               "limits": mix["limits"]}
    with open(os.path.join(root, "bench", "traffic", "tiny.json"), "w") as f:
        json.dump(traffic, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        spec = json.load(f)
    spec["configs"].append({"name": "tiny", "source": "test",
                            "file": "bench/configs/tiny.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "tiny.mix", "config": "tiny",
                              "traffic": "tiny", "chips": 1, "why": "test"})
    with open(path, "w") as f:
        json.dump(spec, f)
    return "tiny.mix"


def make_checkout(root) -> str:
    """A copy of the benchmark's files at ``root``, the engine's sources
    beside them."""
    os.makedirs(root)
    shutil.copytree(BENCH, os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    os.symlink(os.path.join(ROOT, "src"), os.path.join(root, "src"))
    return str(root)


@pytest.fixture
def checkout(tmp_path):
    return make_checkout(tmp_path / "checkout")


def rehearse(root: str, workload: str, seed: int, mode: str = "sound",
             trace: int = 0) -> tuple[dict | None, str]:
    """One CPU run of ``workload`` in a process of its own (``rehearse.py``);
    the result line, or None, and the whole output."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "tests", "rehearse.py"), root,
         workload, str(seed), mode, str(trace)],
        capture_output=True, text=True, env=env, timeout=600)
    out = proc.stdout + proc.stderr
    last = proc.stdout.strip().splitlines()[-1:] if proc.returncode == 0 \
        else []
    return (json.loads(last[0]) if last else None), out
