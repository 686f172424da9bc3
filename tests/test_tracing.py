"""The engine's tracing: operator scopes reach the compiled program, and
``QueryServer.submit`` writes its phase spans to a profile and to its phase
counters."""
import glob
import re

import pytest

import jax

from repro import serve
from repro.core import tracing
from repro.core.plan import scan
from repro.data import tpch


@pytest.fixture(scope="module")
def server():
    return serve.QueryServer(tpch.generate(0.01, seed=11))


def _scopes(hlo_text: str) -> set[str]:
    """The innermost ``rel.*`` component of every ``op_name``."""
    out = set()
    for op_name in re.findall(r'op_name="([^"]*)"', hlo_text):
        found = [c for c in op_name.split("/") if c in tracing.SCOPES]
        if found:
            out.add(found[-1])
    return out


@pytest.mark.parametrize("qid, scopes", [
    (3, {tracing.JOIN_BUILD, tracing.JOIN_PROBE, tracing.JOIN_TAKE,
         tracing.GROUP_BY}),
    (1, {tracing.GROUP_BY}),
])
def test_operator_scopes_reach_the_compiled_program(server, qid, scopes):
    assert scopes <= _scopes(server.compiled(qid).as_text())


def test_scopes_leave_the_operations_alone():
    """A scope changes ``op_name`` metadata only: the program with the
    scoped operators and with their bare bodies is the same."""
    import jax.numpy as jnp
    from repro.core import relational as rel
    from repro.core.table import Table

    def program(build_index, probe_index):
        def join(keys, probe):
            build = Table({"k": keys, "v": keys * 2},
                          jnp.int32(keys.shape[0]))
            matched, rows = probe_index(build_index(build, build["k"]),
                                        probe, probe >= 0)
            return matched, build["v"][rows]
        keys = jnp.arange(64, dtype=jnp.int64)[::-1]
        probe = jnp.arange(0, 128, 3, dtype=jnp.int64)
        return jax.jit(join).lower(keys, probe).as_text(debug_info=False)

    scoped = program(rel.build_index, rel.probe_index)
    assert scoped == program(rel.build_index.__wrapped__,
                             rel.probe_index.__wrapped__)


def _events(logdir):
    from jax.profiler import ProfileData
    (path,) = glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(ev.name, ev.start_ns, ev.end_ns, dict(ev.stats))
                        for ev in line.events
                        if ev.name.startswith("serve.")]
    return out


def test_submit_spans_nest_in_a_profile(server, tmp_path):
    server.submit(6)                      # compiled outside the profile
    n = server.phase_stats()[tracing.SUBMIT]["count"]
    with jax.profiler.trace(str(tmp_path)):
        server.submit(6)
    events = _events(tmp_path)
    (top,) = [e for e in events if e[0] == tracing.SUBMIT]
    assert top[3] == {"request": n + 1, "template": "q6"}
    inner = {name for name, a, b, _ in events
             if name != tracing.SUBMIT and top[1] <= a <= b <= top[2]}
    assert inner == {tracing.BIND, tracing.LOOKUP, tracing.DISPATCH,
                     tracing.WAIT, tracing.FETCH}


def _counts(srv) -> dict[str, int]:
    return {k: v["count"] for k, v in srv.phase_stats().items()}


def test_phase_counters_count_each_span_once_per_request(server):
    before = _counts(server)
    for _ in range(3):
        server.submit(1)
    after = _counts(server)
    grown = {k: after[k] - before.get(k, 0) for k in after}
    assert grown == {tracing.SUBMIT: 3, tracing.BIND: 3, tracing.LOOKUP: 3,
                     tracing.DISPATCH: 3, tracing.WAIT: 3, tracing.FETCH: 3}
    for v in server.phase_stats().values():
        assert 0 < v["max_s"] <= v["total_s"]


def test_an_overflow_counts_one_rerun():
    """groups_hint=2 undercounts orders: the first run overflows and the
    conservative rerun answers."""
    def lying():
        g = scan("orders").group_by(["o_custkey", "o_orderkey"],
                                    [("n", "count", None)],
                                    exchange="gather", final=True,
                                    groups_hint=2)
        return g.finalize(replicated=True)

    srv = serve.QueryServer(tpch.generate(0.005, seed=11))
    srv.submit(serve.PlanTemplate(lying, name="lying"), infer=True)
    counts = _counts(srv)
    assert srv.overflow_reruns == 1
    assert counts[tracing.RERUN] == 1
    assert counts[tracing.SUBMIT] == counts[tracing.WAIT] == 1


def test_span_counts_a_block_that_raises():
    phases = tracing.Phases()
    with pytest.raises(ValueError):
        with phases.span("serve.bind"):
            raise ValueError("bad binding")
    assert phases.stats()["serve.bind"]["count"] == 1
