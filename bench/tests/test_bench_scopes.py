"""The engine's own tracing in a trace: operator scopes from HLO metadata
(each device operation's ``Op.scope``), ``serve.*`` host spans, and a small
trace recorded on a v5e chip."""
import os

import pytest

import benchkit  # noqa: F401 (puts bench/ and src/ on the path)
from harness import scopes as sc
from harness import trace as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")

HLO = """HloModule jit_run, entry_computation_layout={(s64[8]{0})->s64[8]{0}}

%fused_computation.1 (param_0: s64[8], param_1: s32[4]) -> s64[4] {
  %param_0 = s64[8]{0} parameter(0)
  %param_1 = s32[4]{0} parameter(1)
  ROOT %gather.3 = s64[4]{0} gather(s64[8]{0} %param_0, s32[4]{0} %param_1), offset_dims={}, metadata={op_name="jit(run)/rel.join_take/gather" stack_frame_id=3}
}

%fused_computation.2 (param_0: s64[8]) -> s64[8] {
  %param_0 = s64[8]{0} parameter(0)
  ROOT %add.1 = s64[8]{0} add(s64[8]{0} %param_0, s64[8]{0} %param_0)
}

%compare.4 (a: s64[], b: s64[]) -> pred[] {
  %a = s64[] parameter(0)
  %b = s64[] parameter(1)
  ROOT %lt = pred[] compare(s64[] %a, s64[] %b), direction=LT
}

ENTRY %main.9 (p: s64[8], i: s32[4]) -> (s64[8], s64[4]) {
  %p = s64[8]{0} parameter(0)
  %i = s32[4]{0} parameter(1)
  %fusion.1 = s64[4]{0} fusion(s64[8]{0} %p, s32[4]{0} %i), kind=kLoop, calls=%fused_computation.1
  %fusion.2 = s64[8]{0} fusion(s64[8]{0} %p), kind=kLoop, calls=%fused_computation.2, metadata={op_name="jit(run)/rel.join_probe/jit(searchsorted)/while/body/add"}
  %sort.5 = s64[8]{0} sort(s64[8]{0} %fusion.2), dimensions={0}, is_stable=true, to_apply=%compare.4, metadata={op_name="jit(run)/rel.group_by/rel.compact/jit(argsort)/sort" stack_frame_id=7}
  %custom-call.6 = s64[8]{0} custom-call(s64[8]{0} %sort.5), custom_call_target="tpu_custom_call", backend_config="{}", metadata={op_name="jit(run)/rel.group_by/pallas_call"}
  %scatter.7 = s64[8]{0} scatter(s64[8]{0} %custom-call.6, s32[4]{0} %i, s64[4]{0} %fusion.1), to_apply=%compare.4, metadata={op_name="jit(run)/mul"}
  ROOT %tuple.8 = (s64[8]{0}, s64[4]{0}) tuple(s64[8]{0} %scatter.7, s64[4]{0} %fusion.1)
}
"""


def test_scope_is_the_innermost_rel_component():
    s = tr.hlo_scopes(HLO)
    assert s["fusion.2"] == "rel.join_probe"
    assert s["sort.5"] == "rel.compact"          # a compact inside a group-by
    assert s["custom-call.6"] == "rel.group_by"
    assert s["scatter.7"] is None                # outside every scope
    assert s["tuple.8"] is None                  # no metadata, no fusion
    assert s["fusion.1"] == "rel.join_take"      # no metadata: its root's


def test_the_names_read_are_the_engines():
    """A rename in the engine cannot silently blank a reading."""
    from repro.core import tracing
    assert set(sc.LAUNCH + sc.FETCH) <= set(tracing.SPANS)
    assert all(tr.SCOPE.fullmatch(s) for s in tracing.SCOPES)
    assert all(s.startswith(sc.HOST_SPAN) for s in tracing.SPANS)


XSPACE = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 3000000 }
    events { metadata_id: 2 offset_ps: 5000000 duration_ps: 1000000 }
    events { metadata_id: 3 offset_ps: 8000000 duration_ps: 1000000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 4 offset_ps: 1000000 duration_ps: 8000000 } }
  event_metadata { key: 1 value { id: 1 name: "sort.5" } }
  event_metadata { key: 2 value { id: 2 name: "fusion.1" } }
  event_metadata { key: 3 value { id: 3 name: "scatter.7" } }
  event_metadata { key: 4 value { id: 4 name: "jit_run(1)" } } }
planes { id: 2 name: "/host:CPU"
  lines { id: 7 name: "main" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 12000000 }
    events { metadata_id: 2 offset_ps: 100000 duration_ps: 11800000 }
    events { metadata_id: 3 offset_ps: 200000 duration_ps: 400000 }
    events { metadata_id: 4 offset_ps: 700000 duration_ps: 300000 }
    events { metadata_id: 5 offset_ps: 1000000 duration_ps: 10000000 }
    events { metadata_id: 6 offset_ps: 11000000 duration_ps: 800000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.submit q3" } }
  event_metadata { key: 2 value { id: 2 name: "serve.submit" } }
  event_metadata { key: 3 value { id: 3 name: "serve.bind" } }
  event_metadata { key: 4 value { id: 4 name: "serve.dispatch" } }
  event_metadata { key: 5 value { id: 5 name: "serve.wait" } }
  event_metadata { key: 6 value { id: 6 name: "serve.fetch" } } }
"""


def scoped():
    from jax.profiler import ProfileData
    profile = ProfileData.from_text_proto(XSPACE)
    return sc.Scoped.of(tr.reduce(profile, {3: HLO}), profile)


def test_scoped_device_time_adds_up_to_the_pass():
    s = scoped().summary
    assert [o.scope for o in s.ops] == ["rel.compact", "rel.join_take", None]
    assert s.scope_s("rel.compact") == pytest.approx(3e-6)
    assert s.scope_s("rel.join_take") == pytest.approx(1e-6)
    assert s.scope_s(None) == pytest.approx(1e-6)
    assert s.scope_s("rel.join_build") == 0.0
    total = sum(o.dur_ns for o in s.ops) / 1e9
    assert sum(s.scope_s(x) for x in {o.scope for o in s.ops}) == \
        pytest.approx(total)
    assert s.breakdown()["device_ops"][0] == [
        "q3 rel.compact sort.5 (sort)", pytest.approx(3e-6)]


def test_host_spans_phases_and_named_gaps():
    s = scoped()
    assert [h.name for h in s.host] == [
        "serve.submit", "serve.bind", "serve.dispatch", "serve.wait",
        "serve.fetch"]
    assert s.per_request_ms(sc.LAUNCH) == pytest.approx(0.0007)
    assert s.per_request_ms(sc.FETCH) == pytest.approx(0.0008)
    assert s.per_request_ms(("serve.rerun",)) is None
    gaps: dict[str, float] = {}
    for label, secs in s.idle_gaps():
        gaps[label] = gaps.get(label, 0.0) + secs
    # ns: ops busy 2000-5000, 6000-7000, 9000-10000 of a 1000-13000 request
    assert gaps == pytest.approx({
        "q3 submit": 200e-9, "q3 serve.submit": 300e-9,
        "q3 serve.bind": 400e-9, "q3 serve.dispatch": 300e-9,
        "q3 serve.wait": 5000e-9, "q3 serve.fetch": 800e-9})
    assert sum(gaps.values()) == pytest.approx(
        s.summary.window_s - s.summary.busy_s())


def test_a_recorded_v5e_trace_reduces_with_scopes_and_spans():
    """One Q12 request (a join and a group-by) through ``QueryServer`` at
    sf 0.002 on a v5e chip, gzipped."""
    import gzip
    from jax.profiler import ProfileData
    with gzip.open(os.path.join(DATA, "v5e_scoped.hlo.txt.gz"), "rt") as f:
        hlo = f.read()
    with gzip.open(os.path.join(DATA, "v5e_scoped.xplane.pb.gz")) as f:
        profile = ProfileData.from_serialized_xspace(f.read())
    s = sc.Scoped.of(tr.reduce(profile, {12: hlo}), profile)
    assert len(s.summary.ops) == 192
    assert all(o.qid == 12 for o in s.summary.ops)
    scopes = {o.scope for o in s.summary.ops}
    assert {"rel.join_build", "rel.join_probe", "rel.join_take",
            "rel.group_by"} <= scopes
    assert s.summary.scope_s("rel.join_probe") > s.summary.scope_s(None)
    (req,) = s.summary.spans
    assert [(h.name, h.args) for h in s.host] == [
        ("serve.submit", {"request": 2, "template": "q12"}),
        ("serve.bind", {}), ("serve.lookup", {}), ("serve.dispatch", {}),
        ("serve.wait", {}), ("serve.fetch", {})]
    assert all(req.start_ns <= h.start_ns <= h.end_ns <= req.end_ns
               for h in s.host)
    labels = {label for label, _ in s.idle_gaps()}
    assert {"q12 serve.wait", "q12 serve.fetch"} <= labels
    total = sum(o.dur_ns for o in s.summary.ops) / 1e9
    assert sum(s.summary.scope_s(x) for x in scopes) == pytest.approx(total)
