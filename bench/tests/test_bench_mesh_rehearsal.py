"""A four-chip cell rehearsed on 4 virtual CPU devices, in processes of
their own: the client serves on a mesh, set-up passes the chips' check,
the answers are correct, and a second seed compiles nothing."""
import json
import os
import re
import subprocess
import sys

import pytest

from benchkit import BENCH, add_tiny_cell, make_checkout


def rehearse4(root: str, workload: str, seed: int) -> tuple[dict | None, str]:
    """``rehearse.py`` with 4 virtual devices (``benchkit.rehearse`` runs
    one)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "tests", "rehearse.py"), root,
         workload, str(seed), "sound", "0"],
        capture_output=True, text=True, env=env, timeout=600)
    out = proc.stdout + proc.stderr
    last = proc.stdout.strip().splitlines()[-1:] if proc.returncode == 0 \
        else []
    return (json.loads(last[0]) if last else None), out


@pytest.fixture(scope="module")
def tiny4(tmp_path_factory):
    """The tiny cell (sf 0.01, Q3 and Q5) on four chips."""
    root = make_checkout(tmp_path_factory.mktemp("mesh") / "checkout")
    cell = add_tiny_cell(root, queries=(3, 5))
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        spec = json.load(f)
    for w in spec["workloads"]:
        if w["name"] == cell:
            w["chips"] = 4
    with open(path, "w") as f:
        json.dump(spec, f)
    return root, cell


def setup_line(out):
    m = re.search(r"# setup: (\d+) programs, (\d+) compiled, (\d+) read", out)
    return tuple(map(int, m.groups()))


def test_a_four_chip_cell_runs_on_a_mesh_and_a_second_seed_compiles_nothing(
        tiny4):
    root, cell = tiny4
    first, out1 = rehearse4(root, cell, 2**31 + 17)
    assert first is not None and first["correct"], out1
    assert "count=4" in out1 and setup_line(out1)[1] > 0
    second, out2 = rehearse4(root, cell, 2**33 + 18)
    assert second is not None and second["correct"], out2
    programs, compiled, read = setup_line(out2)
    assert compiled == 0 and read == programs > 0
    assert re.search(r"# window: \d+ requests in [\d.]+ s, 0 compiled, 0 read",
                     out2)
    assert second["device"]["count"] == 4 and second["failed"] == 0
