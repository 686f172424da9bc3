"""A run measures a TPU listed in peaks.json, or fails without a result."""
import os
import shutil
import subprocess
import sys
import types

import pytest

from benchkit import BENCH, ROOT, checkout  # noqa: F401
from harness import device

V5E = "TPU v5 lite"


def fake(platform, kind):
    return types.SimpleNamespace(platform=platform, device_kind=kind)


def test_peaks_name_their_source():
    peaks = device.load_peaks()
    assert peaks[V5E]["hbm_bytes_per_s"] == 819e9
    assert peaks[V5E]["bf16_flops_per_s"] == 197e12
    assert "TPU v5e" in peaks[V5E]["source"]


def test_a_listed_tpu_is_measured():
    devs, peaks = device.chips([fake("tpu", V5E)] * 4, 1,
                               device.load_peaks())
    assert len(devs) == 1 and peaks["hbm_bytes"] == 16e9


@pytest.mark.parametrize("devices,count,match", [
    ([fake("cpu", "cpu")], 1, "no TPU"),
    ([], 1, "no TPU"),
    ([fake("tpu", "TPU v99")], 1, "no peaks"),
    ([fake("tpu", V5E)], 4, "4 chips"),
])
def test_no_number_without_a_known_chip(devices, count, match):
    with pytest.raises(device.NoChip, match=match):
        device.chips(devices, count, device.load_peaks())


def run_cli(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "tpch_sf1.join",
         "--seed", str(2**31 + 3), "--seconds", "1", "--trace", "0"],
        cwd=cwd, capture_output=True, text=True, env=env, timeout=300)


def test_on_the_cpu_the_run_fails_and_prints_no_result(checkout):
    proc = run_cli(checkout)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert '"correct"' not in proc.stdout


def test_the_benchmark_files_alone_do_not_make_a_result(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    proc = run_cli(str(tmp_path))
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout
