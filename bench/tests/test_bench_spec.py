"""BENCHMARK.json and the files it names: found by name, added as files."""
import json
import os
import re

import pytest

from benchkit import ROOT, add_tiny_cell, checkout  # noqa: F401
from harness.spec import Bench

SPEC = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def test_every_cell_is_found_with_its_files():
    bench = Bench(ROOT)
    for w in SPEC["workloads"]:
        cell = bench.cell(w["name"])
        assert cell.config["name"] == w["config"]
        assert cell.traffic.queries
        names = {m.name for m in cell.metrics}
        assert "setup_s" in names
        assert any(m.kind == "per_layer" for m in cell.metrics)
        for q in cell.traffic.queries:     # a reference for every query
            assert os.path.exists(os.path.join(
                ROOT, "bench", "references", cell.config["reference"],
                f"q{q:02d}.py"))


def test_names_and_entries_keep_to_the_contract():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in SPEC[group]]
        assert len(set(names)) == len(names)
        assert all(NAME.match(n) for n in names)
    used = {w["config"] for w in SPEC["workloads"]}
    assert used == {c["name"] for c in SPEC["configs"]}
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e
    cells = {w["name"] for w in SPEC["workloads"]}
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e
        assert set(m.get("workloads", cells)) <= cells
    for c in SPEC["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            conf = json.load(f)
        assert set(c["reduced"]) <= set(conf) and set(c["reduced"]) == set(
            conf["reduced"])


def test_a_cell_and_a_metric_added_as_files_are_found(checkout):
    workload = add_tiny_cell(checkout)
    with open(os.path.join(checkout, "bench", "metrics", "answers_n.py"),
              "w") as f:
        f.write("LAYER, UNIT, MOVES = 'serving', 'requests', 'pass_s'\n"
                "def read(run):\n    return len(run.executions)\n")
    path = os.path.join(checkout, "BENCHMARK.json")
    with open(path) as f:
        spec = json.load(f)
    spec["per_layer"].append({"name": "answers_n", "unit": "requests",
                              "better": "higher", "source": "host_clock",
                              "layer": "serving", "moves": "pass_s",
                              "workloads": [workload]})
    with open(path, "w") as f:
        json.dump(spec, f)
    cell = Bench(checkout).cell(workload)
    assert cell.config["scale_factor"] == 0.01
    assert cell.traffic.queries == (6, 3)
    added = [m for m in cell.metrics if m.name == "answers_n"]
    assert added and added[0].read(type("R", (), {"executions": [1, 2]})) == 2
    other = Bench(checkout).cell(SPEC["workloads"][0]["name"])
    assert "answers_n" not in {m.name for m in other.metrics}


def test_sort_ms_is_read_in_the_join_cell_only():
    bench = Bench(ROOT)
    join = {m.name for m in bench.cell("tpch_sf1.join").metrics}
    scan = {m.name for m in bench.cell("tpch_sf1.scan_agg").metrics}
    assert "sort_ms" in join and "sort_ms" not in scan
    assert "compact_ms" in join and "compact_ms" not in scan
    for name in ("group_by_ms", "join_probe_ms", "join_take_ms",
                 "join_build_ms", "order_ms", "unscoped_ms"):
        assert name in join and name in scan


def test_an_unknown_cell_is_an_error():
    with pytest.raises(KeyError, match="no workload"):
        Bench(ROOT).cell("nope.none")
