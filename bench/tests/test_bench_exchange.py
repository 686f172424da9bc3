"""The exchange layer's readers: ``collective_ms`` (class ``collective``)
and ``exchange_ms`` (scope ``rel.exchange``), summed over the chips, on a
synthetic four-chip trace."""
import json
import os

import pytest

from benchkit import ROOT
from harness.spec import Bench
from harness.trace import Op, Span, Summary
from test_bench_metrics import reader, run_of

MS = 1e6
CELL = "tpch_sf4_4chip.join"


def four_chips():
    """One 10 ms request on four chips.  Each chip runs a 1 ms all-to-all
    and a 2 ms unpack inside ``rel.exchange`` and a 3 ms group-by; chip 3's
    all-to-all waits 1 ms longer."""
    ops = []
    for c in range(4):
        dev = f"/device:TPU:{c}"
        ops += [Op("all-to-all.1", 1, "collective", 0, (2 if c == 3 else 1)
                   * MS, dev, scope="rel.exchange"),
                Op("fusion.2", 1, "gather_scatter", 3 * MS, 5 * MS, dev,
                   scope="rel.exchange"),
                Op("fusion.3", 1, "gather_scatter", 5 * MS, 8 * MS, dev,
                   scope="rel.group_by")]
    return Summary(ops, [Span(1, 0, 10 * MS)])


def test_collective_and_exchange_time_add_up_over_the_chips():
    run = run_of([(1, 0.01)], trace=four_chips(), chips=4)
    assert reader("collective_ms").read(run) == pytest.approx(5.0)
    assert reader("exchange_ms").read(run) == pytest.approx(13.0)
    assert reader("exchange_ms").read(run) >= \
        reader("collective_ms").read(run)


def test_a_trace_without_exchanges_reads_nothing():
    one_chip = Summary([Op("fusion.3", 1, "gather_scatter", 0, MS,
                           "/device:TPU:0", scope="rel.group_by")],
                       [Span(1, 0, 10 * MS)])
    run = run_of([(1, 0.01)], trace=one_chip)
    assert reader("collective_ms").read(run) is None
    assert reader("exchange_ms").read(run) is None


def test_the_four_chip_cell_reads_the_exchange_layer():
    bench = Bench(ROOT)
    cell = bench.cell(CELL)
    assert cell.chips == 4 and cell.config["chips"] == 4
    names = {m.name for m in cell.metrics}
    assert {"collective_ms", "exchange_ms", "setup_s", "pass_s",
            "query_geomean_ms"} <= names
    spec = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    for m in spec["per_layer"]:
        if m["name"] in ("collective_ms", "exchange_ms"):
            assert m["workloads"] == [CELL]
    for w in spec["workloads"]:
        if w["name"] != CELL:
            assert not {"collective_ms", "exchange_ms"} & {
                m.name for m in bench.cell(w["name"]).metrics}
