"""QueryServer on a mesh of 4 virtual CPU devices: hash-partitioned tables
placed once, one SPMD program per template, exchanges as collectives inside
the ``rel.exchange`` scope.  Everything runs in one subprocess, so the
device-count XLA flag never leaks into the main test process; the tests
read its report."""
import json
import os
import subprocess
import sys

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
QUERIES = (18, 3, 10, 5, 1, 6, 13)

_SCRIPT = r"""
import json, sys
import numpy as np
import jax
sys.path.insert(0, "bench")
from harness import trace as tr
from repro.core import backend as B
from repro.core.table import days
from repro.data import tpch
from repro.serve import QueryServer
from repro.serve import server as S
from repro.serve.templates import TEMPLATES

BINDINGS = {
    3: [{"q3_date": days("1995-03-01") + d} for d in (0, 15, 30)],
    5: [{"q5_date_lo": days(f"{y}-01-01"), "q5_date_hi": days(f"{y + 1}-01-01")}
        for y in (1993, 1995, 1997)],
    1: TEMPLATES[1].samples[:3],
    6: TEMPLATES[6].samples[:3],
}
db = tpch.generate(0.01, seed=11)
mesh, one = QueryServer(db, devices=4), QueryServer(db, devices=1)
report = {"answers": {}, "traces": {}, "spans": {}, "scopes": {},
          "exchanges": {}, "one_chip": {}}
for q in QUERIES:
    t = TEMPLATES[q]
    errors = []
    before = mesh.recompiles
    for b in BINDINGS.get(q, [{}]):
        want, _ = B.run_reference(t.bind(**b), db)
        got, alone = mesh.submit(q, b), one.submit(q, b)
        for k in want:
            for other, label in ((want, "reference"), (alone, "one chip")):
                try:
                    assert len(got[k]) == len(other[k])
                    np.testing.assert_allclose(
                        np.asarray(got[k], np.float64),
                        np.asarray(other[k], np.float64), rtol=1e-7)
                except AssertionError as e:
                    errors.append(f"{b} {k} vs {label}: {e}")
    program = mesh.compiled(q)
    report["answers"][q] = errors
    report["traces"][q] = mesh.recompiles - before
    report["spans"][q] = sorted({d.id for s in jax.tree.leaves(
        program.input_shardings) for d in s.device_set})
    text = program.as_text()
    classes, scopes = tr.hlo_classes(text), tr.hlo_scopes(text)
    report["scopes"][q] = sorted({scopes[n] or "-" for n, c in classes.items()
                                  if c == "collective"})
    report["exchanges"][q] = [mesh.exchanges(q), t.query.static_counts(),
                              one.exchanges(q)]
    binding = {n: jax.numpy.asarray(v, S._PDTYPE[t.params[n].dtype])
               for n, v in t.bind().values.items()}
    report["one_chip"][q] = {
        "collectives": sorted(n for n, c in tr.hlo_classes(
            one.compiled(q).as_text()).items() if c == "collective"),
        "manual": ["manual_computation" in srv._executable(
            t, True, srv.capacity_factor).lower(srv._tables, binding).as_text()
            for srv in (one, mesh)]}
report["recompiles"] = [mesh.recompiles, len(QUERIES)]
report["footprint"] = [mesh.footprint_bytes(0.0), one.footprint_bytes(0.0),
                       sum(np.asarray(c).nbytes for tb in db.tables.values()
                           for c in tb.values())]
# a chip lost: the partitions move to the first 3, and Q5 answers there
places = mesh.phase_stats()["serve.place"]["count"]
mesh.degrade(3)
want, _ = B.run_reference(TEMPLATES[5].bind(), db)
got = mesh.submit(5, {})
report["degraded"] = {
    "places": [places, mesh.phase_stats()["serve.place"]["count"]],
    "spans": sorted({d.id for s in jax.tree.leaves(
        mesh.compiled(5).input_shardings) for d in s.device_set}),
    "footprint": mesh.footprint_bytes(0.0),
    "answer": all(np.allclose(np.asarray(got[k], np.float64),
                              np.asarray(want[k], np.float64), rtol=1e-7)
                  for k in want)}
print("REPORT " + json.dumps(report))
"""


@pytest.fixture(scope="module")
def report():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(_ROOT, "src"))
    script = f"QUERIES = {QUERIES!r}\n" + _SCRIPT
    r = subprocess.run([sys.executable, "-c", script], env=env, cwd=_ROOT,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, f"{r.stdout}\n{r.stderr[-4000:]}"
    line = next(ln for ln in r.stdout.splitlines()
                if ln.startswith("REPORT "))
    return {k: {int(q) if q.isdigit() else q: v for q, v in d.items()}
            if isinstance(d, dict) else d
            for k, d in json.loads(line[len("REPORT "):]).items()}


@pytest.mark.parametrize("qid", QUERIES)
def test_mesh_server_answers_as_the_reference_and_one_chip(report, qid):
    """Q18/Q3/Q10/Q5 (the join cell), Q1 (final gather), Q6 (all-reduce)
    and Q13 (shuffle into a hash group-by), at up to three bindings each."""
    assert report["answers"][qid] == []


def test_each_template_traces_once_across_bindings(report):
    assert all(n == 1 for n in report["traces"].values()), report["traces"]
    mesh, templates = report["recompiles"]
    assert mesh == templates        # compiled() read the same trace


def test_every_program_takes_its_inputs_on_the_four_devices(report):
    assert all(on == [0, 1, 2, 3] for on in report["spans"].values())


def test_every_collective_is_in_the_exchange_scope(report):
    from repro.core import tracing
    assert all(s == [tracing.EXCHANGE] for s in report["scopes"].values()), \
        report["scopes"]


def test_exchange_counters_equal_the_plans_static_counts(report):
    names = {"shuffle": "shuffles", "broadcast": "broadcasts",
             "gather": "final_gathers", "allreduce": "allreduces"}
    for qid, (got, static, one_chip) in report["exchanges"].items():
        assert {names[k]: v for k, v in got.items() if k in names} == static
        shipped = sum(v for k, v in got.items() if k != "allreduce"
                      and k != "bytes")
        assert (got["bytes"] > 0) == (shipped > 0), qid
        assert one_chip is None       # one chip exchanges nothing


def test_one_chip_programs_hold_no_collective_and_no_shard_map(report):
    for qid, one in report["one_chip"].items():
        assert one["collectives"] == [], qid
        assert one["manual"] == [False, True], qid   # one chip, then mesh


def test_footprint_reads_the_placed_partitions(report):
    mesh, one, columns = report["footprint"]
    assert one >= columns                       # padded to a multiple of 8
    assert columns / 4 < mesh < columns / 2     # a quarter, and the replicas


def test_a_server_wider_than_the_devices_jax_sees_is_refused():
    """This process sees one CPU device: no mesh of two, and no fallback."""
    from repro.data import tpch
    from repro.serve import QueryServer
    with pytest.raises(ValueError, match="JAX sees 1"):
        QueryServer(tpch.generate(0.002, seed=11), devices=2)


def test_a_lost_chip_moves_the_partitions_to_the_survivors(report):
    deg = report["degraded"]
    assert deg["places"] == [1, 2]              # construction, degrade
    assert deg["spans"] == [0, 1, 2] and deg["answer"]
    assert deg["footprint"] > report["footprint"][0]
