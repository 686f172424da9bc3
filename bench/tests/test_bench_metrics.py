"""Metric readers: end-to-end arithmetic and the per-layer reductions."""
import gzip
import importlib.util
import json
import math
import os

import pytest

from benchkit import BENCH, ROOT
from harness import trace as tr
from harness.client import Execution
from harness.runner import Run
from harness.trace import Op, Span, Summary

SPEC = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))


def reader(name):
    spec = importlib.util.spec_from_file_location(
        f"metric_{name}", os.path.join(BENCH, "metrics", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def run_of(latencies, trace=None, peaks=None, setup_s=42.0, chips=1):
    t, execs = 0.0, []
    for qid, lat in latencies:
        execs.append(Execution(qid, {}, t, t + lat, {}))
        t += lat
    return Run(executions=execs, window_s=t, setup_s=setup_s,
               peaks=peaks, column_bytes={"a": 800, "b": 200},
               query_columns={1: ["a"], 2: ["a", "b"]}, chips=chips,
               trace=trace)


def test_geomean_and_pass_arithmetic():
    run = run_of([(1, 1.0), (2, 4.0), (1, 1.0)])   # means: q1 1 s, q2 4 s
    assert reader("query_geomean_ms").read(run) == pytest.approx(2000.0)
    assert reader("pass_s").read(run) == pytest.approx(5.0)
    assert reader("setup_s").read(run) == 42.0


def test_a_stall_lands_in_the_metrics():
    calm = run_of([(1, 1.0), (2, 4.0), (1, 1.0), (2, 4.0)])
    stall = run_of([(1, 1.0), (2, 4.0), (1, 4.0), (2, 4.0)])  # +3 s once
    g, p = reader("query_geomean_ms"), reader("pass_s")
    assert p.read(stall) - p.read(calm) == pytest.approx(1.5)
    assert g.read(stall) == pytest.approx(1e3 * math.sqrt(2.5 * 4.0))


def trace_of():
    """Two requests of 10 ms; the device is busy 4 + 3 ms in the first and
    2 ms in the second."""
    ms = 1e6
    spans = [Span(1, 0, 10 * ms), Span(2, 10 * ms, 20 * ms)]
    ops = [Op("sort.1", 1, "sort", 1 * ms, 5 * ms, "/device:TPU:0"),
           Op("fusion.2", 1, "gather_scatter", 4 * ms, 8 * ms,
              "/device:TPU:0"),
           Op("custom-call.3", 2, "kernel", 12 * ms, 14 * ms,
              "/device:TPU:0")]
    return Summary(ops, spans)


def test_per_layer_readers_on_a_trace():
    run = run_of([(1, 0.01), (2, 0.01)], trace=trace_of(),
                 peaks={"hbm_bytes_per_s": 1e6})
    assert reader("sort_ms").read(run) == pytest.approx(4.0)
    assert reader("gather_scatter_ms").read(run) == pytest.approx(4.0)
    assert reader("kernel_ms").read(run) == pytest.approx(2.0)
    # busy 7 + 2 ms of 20 ms
    assert reader("device_idle_share").read(run) == pytest.approx(55.0)
    # host: (10 - 7) and (10 - 2) ms
    assert reader("submit_host_ms").read(run) == pytest.approx(5.5)
    # q1 reads 800 B, q2 1000 B: 1800 B at 1 MB/s is 1.8 ms of 20 ms
    assert reader("hbm_roofline_share").read(run) == pytest.approx(9.0)


def test_the_roofline_is_the_cells_chips_together():
    four = run_of([(1, 0.01), (2, 0.01)], trace=trace_of(),
                  peaks={"hbm_bytes_per_s": 1e6}, chips=4)
    # 1800 B at 4 x 1 MB/s is 0.45 ms of 20 ms
    assert reader("hbm_roofline_share").read(four) == pytest.approx(2.25)


def test_readers_with_nothing_to_read_return_nothing():
    bare = run_of([(1, 1.0)])
    nothing = Summary([], [Span(1, 0, 1e9)])
    for m in SPEC["per_layer"]:
        assert reader(m["name"]).read(bare) is None
        assert reader(m["name"]).read(run_of([(1, 1.0)], trace=nothing,
                                             peaks={"hbm_bytes_per_s": 1})) \
            is None
    only_kernel = Summary([Op("k", 1, "kernel", 0, 1e6, "/device:TPU:0")],
                          [Span(1, 0, 1e9)])
    run = run_of([(1, 1.0)], trace=only_kernel)
    assert reader("sort_ms").read(run) is None
    assert reader("kernel_ms").read(run) == pytest.approx(1.0)


SCOPE_READERS = [m["name"] for m in SPEC["per_layer"]
                 if hasattr(reader(m["name"]), "SCOPE")]


@pytest.fixture(scope="module")
def v5e_scoped():
    """One Q12 request through ``QueryServer`` at sf 0.002, traced on a v5e
    chip: its reduction as a run's trace, and the scopes of its HLO."""
    from jax.profiler import ProfileData
    data = os.path.join(BENCH, "tests", "data")
    with gzip.open(os.path.join(data, "v5e_scoped.hlo.txt.gz"), "rt") as f:
        hlo = f.read()
    with gzip.open(os.path.join(data, "v5e_scoped.xplane.pb.gz")) as f:
        profile = ProfileData.from_serialized_xspace(f.read())
    return tr.reduce(profile, {12: hlo}), tr.hlo_scopes(hlo)


def test_every_engine_scope_has_a_reader():
    """A rename in the engine cannot silently blank a reading, and no
    operator's time goes unread."""
    from repro.core import tracing
    scopes = [reader(name).SCOPE for name in SCOPE_READERS]
    assert set(s for s in scopes if s) == set(tracing.SCOPES)
    assert scopes.count(None) == 1 and len(set(scopes)) == len(scopes)


@pytest.mark.parametrize("name", SCOPE_READERS)
def test_a_scope_reader_reads_its_scopes_self_time(v5e_scoped, name):
    summary, scope_of = v5e_scoped
    scope = reader(name).SCOPE
    own = sum(o.dur_ns for o in summary.ops
              if scope_of.get(o.name) == scope) / 1e6
    got = reader(name).read(run_of([(12, 1.0)], trace=summary))
    if own == 0:                 # e.g. q12 compacts nothing
        assert got is None
    else:
        assert got == pytest.approx(own)
        assert got == pytest.approx(1e3 * summary.scope_s(scope))


def test_the_scope_readers_add_up_to_the_device_time(v5e_scoped):
    summary, _ = v5e_scoped
    run = run_of([(12, 1.0)], trace=summary)
    total = sum(reader(name).read(run) or 0.0 for name in SCOPE_READERS)
    assert total == pytest.approx(
        sum(o.dur_ns for o in summary.ops) / 1e6, rel=1e-12)


@pytest.mark.parametrize("entry", SPEC["per_layer"], ids=lambda m: m["name"])
def test_reader_declares_what_benchmark_json_says(entry):
    mod = reader(entry["name"])
    assert (mod.LAYER, mod.UNIT, mod.MOVES) == (
        entry["layer"], entry["unit"], entry["moves"])


@pytest.mark.parametrize("entry", SPEC["end_to_end"], ids=lambda m: m["name"])
def test_every_end_to_end_metric_has_a_reader(entry):
    assert reader(entry["name"]).UNIT == entry["unit"]
