"""Whole runs on the CPU at sf 0.01, in processes of their own: the second
seed compiles nothing, and the check rejects a broken timed path and the
float32 control."""
import re

import pytest

from benchkit import add_tiny_cell, make_checkout, rehearse


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    root = make_checkout(tmp_path_factory.mktemp("rehearsal") / "checkout")
    return root, add_tiny_cell(root)


def setup_line(out):
    m = re.search(r"# setup: (\d+) programs, (\d+) compiled, (\d+) read", out)
    return tuple(map(int, m.groups()))


def test_a_second_seed_compiles_nothing(tiny):
    root, cell = tiny
    first, out1 = rehearse(root, cell, 2**31 + 11)
    second, out2 = rehearse(root, cell, 2**33 + 12)
    assert first["correct"] and second["correct"], out1 + out2
    assert setup_line(out1)[1] > 0
    programs, compiled, read = setup_line(out2)
    assert compiled == 0 and read == programs > 0
    assert re.search(r"# window: \d+ requests in [\d.]+ s, 0 compiled, 0 read",
                     out2)
    assert set(second["metrics"]) == {"query_geomean_ms", "pass_s",
                                      "setup_s"}
    assert list(second)[-1] == "check"
    assert second["attempted"] >= 2 and second["failed"] == 0


def test_a_traced_cpu_run_reports_no_device_metric(tiny):
    root, cell = tiny
    res, out = rehearse(root, cell, 31, trace=1)
    assert res["correct"], out
    assert res["metrics"] == {}
    assert res["device"]["platform"] == "cpu" and res["device"]["busy_s"] == 0


@pytest.mark.parametrize("mode", ["alter", "drop", "control"])
def test_the_check_rejects_a_broken_timed_path(tiny, mode):
    root, cell = tiny
    res, out = rehearse(root, cell, 47, mode=mode)
    assert res is not None, out
    assert res["correct"] is False
    numbers = res["check"]
    assert any(n["value"] > n["limit"] for n in numbers.values())
