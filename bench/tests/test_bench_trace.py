"""The trace reduction: HLO opcode classes, device busy and idle time on
every chip, and small profiler traces recorded on a v5e chip."""
import collections
import gzip
import os

import pytest

import benchkit  # noqa: F401 (puts bench/ and src/ on the path)
from harness import trace as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")

HLO = """HloModule jit_run, entry_computation_layout={(s64[8]{0})->s64[8]{0}}

%fused_computation.1 (param_0: s64[8], param_1: s32[4]) -> s64[4] {
  %param_0 = s64[8]{0:T(1024)} parameter(0)
  %param_1 = s32[4]{0} parameter(1)
  ROOT %gather.3 = s64[4]{0} gather(s64[8]{0} %param_0, s32[4]{0} %param_1), offset_dims={}
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
  %fusion.2 = s64[8]{0} fusion(s64[8]{0} %p), kind=kLoop, calls=%fused_computation.2
  %sort.5 = s64[8]{0} sort(s64[8]{0} %fusion.2), dimensions={0}, is_stable=true, to_apply=%compare.4
  %custom-call.6 = s64[8]{0} custom-call(s64[8]{0} %sort.5), custom_call_target="tpu_custom_call", backend_config="{}"
  %scatter.7 = s64[8]{0} scatter(s64[8]{0} %custom-call.6, s32[4]{0} %i, s64[4]{0} %fusion.1), to_apply=%compare.4
  ROOT %tuple.8 = (s64[8]{0}, s64[4]{0}) tuple(s64[8]{0} %scatter.7, s64[4]{0} %fusion.1)
}
"""


def test_hlo_classes_come_from_opcodes_inside_fusions():
    c = tr.hlo_classes(HLO)
    assert c["fusion.1"] == "gather_scatter"      # named fusion, holds gather
    assert c["fusion.2"] == "other"
    assert c["sort.5"] == "sort"
    assert c["custom-call.6"] == "kernel"
    assert c["scatter.7"] == "gather_scatter"
    assert c["tuple.8"] == "other"


def ms(a, b):
    return a * 1e6, b * 1e6


def summary():
    spans = [tr.Span(1, *ms(0, 10)), tr.Span(2, *ms(12, 20))]
    ops = [tr.Op("a", 1, "sort", *ms(1, 4), "/device:TPU:0"),
           tr.Op("b", 1, "other", *ms(3, 6), "/device:TPU:0"),
           tr.Op("c", 2, "kernel", *ms(13, 19), "/device:TPU:0")]
    return tr.Summary(ops, spans)


def test_busy_is_a_union_and_gaps_are_named_by_host_spans():
    s = summary()
    assert s.window_s == pytest.approx(0.020)
    assert s.busy_s() == pytest.approx(0.011)           # 1-6 and 13-19
    assert s.busy_within(s.spans[0]) == pytest.approx(0.005)
    gaps = s.idle_gaps()
    assert gaps[0] == ("q1 submit", pytest.approx(0.004))  # 6-10
    assert ("between requests", pytest.approx(0.002)) in gaps  # 10-12
    assert sum(g for _, g in gaps) == pytest.approx(0.020 - 0.011)
    b = s.breakdown()
    assert b["device_ops"][0] == ["q2 c (kernel)", pytest.approx(0.006)]
    assert len(b["idle_gaps"]) <= 10


XSPACE = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 3000000 }
    events { metadata_id: 2 offset_ps: 5000000 duration_ps: 2000000 }
    events { metadata_id: 3 offset_ps: 12000000 duration_ps: 1000000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 4 offset_ps: 1000000 duration_ps: 6000000 } }
  event_metadata { key: 1 value { id: 1 name: "sort.5" } }
  event_metadata { key: 2 value { id: 2 name: "fusion.1" } }
  event_metadata { key: 3 value { id: 3 name: "fusion.2" } }
  event_metadata { key: 4 value { id: 4 name: "jit_run(1)" } } }
planes { id: 2 name: "/host:CPU"
  lines { id: 7 name: "main" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 9000000 }
    events { metadata_id: 2 offset_ps: 10000000 duration_ps: 4000000 }
    events { metadata_id: 3 offset_ps: 0 duration_ps: 100 } }
  event_metadata { key: 1 value { id: 1 name: "bench.submit q3" } }
  event_metadata { key: 2 value { id: 2 name: "bench.submit q6" } }
  event_metadata { key: 3 value { id: 3 name: "PjitFunction(run)" } } }
"""


def test_reduce_reads_device_ops_and_host_spans():
    from jax.profiler import ProfileData
    s = tr.reduce(ProfileData.from_text_proto(XSPACE), {3: HLO, 6: HLO})
    assert [(sp.qid, sp.start_ns, sp.end_ns) for sp in s.spans] == [
        (3, 1000, 10000), (6, 11000, 15000)]
    assert [(o.name, o.qid, o.cls) for o in s.ops] == [
        ("sort.5", 3, "sort"), ("fusion.1", 3, "gather_scatter"),
        ("fusion.2", 6, "other")]               # modules are not ops
    assert s.window_s == pytest.approx(14e-6)
    assert s.busy_s() == pytest.approx(6e-6)
    assert s.class_s("sort") == pytest.approx(3e-6)


def test_a_recorded_v5e_trace_reduces():
    from jax.profiler import ProfileData
    with open(os.path.join(DATA, "v5e_small.hlo.txt")) as f:
        hlo = f.read()
    s = tr.reduce(ProfileData.from_file(
        os.path.join(DATA, "v5e_small.xplane.pb")), {0: hlo})
    assert s.devices == ["/device:TPU:0"]
    assert len(s.spans) == 1 and len(s.ops) == 27
    assert all(o.qid == 0 for o in s.ops)    # device clock aligned to host
    by = {o.name: o.cls for o in s.ops}
    assert by["sort.11"] == "sort"
    assert {"sort", "gather_scatter", "other"} == set(by.values())
    assert 0 < s.busy_s() <= s.window_s
    assert sum(o.dur_ns for o in s.ops) / 1e9 == pytest.approx(s.busy_s())


SKEWED = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 2000000 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 10260000 duration_ps: 30000 }
    events { metadata_id: 3 offset_ps: 11000000 duration_ps: 1000000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 4 offset_ps: 900000 duration_ps: 50000 }
    events { metadata_id: 4 offset_ps: 1500000 duration_ps: 8800000 }
    events { metadata_id: 4 offset_ps: 10400000 duration_ps: 3600000 } }
  event_metadata { key: 1 value { id: 1 name: "sort.5" } }
  event_metadata { key: 2 value { id: 2 name: "fusion.1" } }
  event_metadata { key: 3 value { id: 3 name: "fusion.2" } }
  event_metadata { key: 4 value { id: 4 name: "jit_run(1)" } } }
planes { id: 2 name: "/host:CPU"
  lines { id: 7 name: "main" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 9200000 }
    events { metadata_id: 2 offset_ps: 10250000 duration_ps: 4750000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.submit q3" } }
  event_metadata { key: 2 value { id: 2 name: "bench.submit q6" } } }
"""


def test_an_operation_is_charged_to_the_program_execution_that_holds_it():
    """The clocks cannot be aligned here (an execution starts before any
    span, one ends after its span), and the first program's last operation
    starts after its request's span has closed: it is still q3's."""
    from jax.profiler import ProfileData
    s = tr.reduce(ProfileData.from_text_proto(SKEWED), {3: HLO, 6: HLO})
    assert [(o.name, o.qid) for o in s.ops] == [
        ("sort.5", 3), ("fusion.1", 3), ("fusion.2", 6)]

COLLECTIVE_HLO = """HloModule jit_spmd, entry_computation_layout={(s64[8]{0})->s64[8]{0}}

%async_computation (param_0: s64[8]) -> s64[8] {
  %param_0 = s64[8]{0} parameter(0)
  ROOT %all-to-all.1 = s64[8]{0} all-to-all(s64[8]{0} %param_0), replica_groups={{0,1}}, dimensions={0}
}

%fused_computation.3 (param_0: s64[8]) -> s64[8] {
  %param_0 = s64[8]{0} parameter(0)
  %sort.1 = s64[8]{0} sort(s64[8]{0} %param_0), dimensions={0}, to_apply=%compare.4
  ROOT %all-reduce.2 = s64[8]{0} all-reduce(s64[8]{0} %sort.1), replica_groups={{0,1}}, to_apply=%add.5
}

%fused_computation.4 (param_0: s64[8], param_1: s32[4]) -> s64[4] {
  %param_0 = s64[8]{0} parameter(0)
  %param_1 = s32[4]{0} parameter(1)
  %gather.2 = s64[4]{0} gather(s64[8]{0} %param_0, s32[4]{0} %param_1), offset_dims={}
  ROOT %reduce-scatter.3 = s64[2]{0} reduce-scatter(s64[4]{0} %gather.2), replica_groups={{0,1}}, dimensions={0}, to_apply=%add.5
}

ENTRY %main.9 (p: s64[8], i: s32[4]) -> s64[16] {
  %p = s64[8]{0} parameter(0)
  %i = s32[4]{0} parameter(1)
  %all-to-all-start = ((s64[8]{0}), s64[8]{0}) async-start(s64[8]{0} %p), calls=%async_computation
  %all-to-all-done = s64[8]{0} async-done(((s64[8]{0}), s64[8]{0}) %all-to-all-start), calls=%async_computation
  %all-gather-start.1 = (s64[8]{0}, s64[16]{0}) all-gather-start(s64[8]{0} %all-to-all-done), replica_groups={{0,1}}, dimensions={0}
  %all-gather-done.1 = s64[16]{0} all-gather-done((s64[8]{0}, s64[16]{0}) %all-gather-start.1)
  %collective-permute.2 = s64[8]{0} collective-permute(s64[8]{0} %p), source_target_pairs={{0,1},{1,0}}
  %fusion.3 = s64[8]{0} fusion(s64[8]{0} %p), kind=kLoop, calls=%fused_computation.3
  %fusion.4 = s64[2]{0} fusion(s64[8]{0} %p, s32[4]{0} %i), kind=kLoop, calls=%fused_computation.4
  ROOT %add.6 = s64[16]{0} add(s64[16]{0} %all-gather-done.1, s64[16]{0} %all-gather-done.1)
}
"""


def test_exchanges_between_chips_are_collectives():
    c = tr.hlo_classes(COLLECTIVE_HLO)
    for name in ("all-to-all-start", "all-to-all-done", "all-gather-start.1",
                 "all-gather-done.1", "collective-permute.2", "fusion.4"):
        assert c[name] == "collective", name
    assert c["fusion.3"] == "sort"            # a sort outranks a collective
    assert c["add.6"] == "other"
    assert tr.classify({"gather", "all-reduce-start"}) == "collective"
    assert tr.classify({"tpu_custom_call", "all-gather"}) == "kernel"
    assert tr.classify({"reduce", "scatter"}) == "gather_scatter"


TWO_CHIPS = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 4000000 duration_ps: 3000000 } }
  event_metadata { key: 1 value { id: 1 name: "all-to-all-start" } }
  event_metadata { key: 2 value { id: 2 name: "fusion.3" } } }
planes { id: 2 name: "/device:TPU:1"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 1000000 duration_ps: 4000000 }
    events { metadata_id: 2 offset_ps: 6000000 duration_ps: 1000000 } }
  event_metadata { key: 1 value { id: 1 name: "all-to-all-start" } }
  event_metadata { key: 2 value { id: 2 name: "fusion.4" } } }
planes { id: 3 name: "/host:CPU"
  lines { id: 7 name: "main" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.submit q10" } } }
"""


def test_every_chip_is_read_and_collectives_add_up_over_chips():
    from jax.profiler import ProfileData
    s = tr.reduce(ProfileData.from_text_proto(TWO_CHIPS),
                  {10: COLLECTIVE_HLO})
    assert s.devices == ["/device:TPU:0", "/device:TPU:1"]
    assert [(o.name, o.device[-1], o.cls) for o in s.ops] == [
        ("all-to-all-start", "0", "collective"), ("fusion.3", "0", "sort"),
        ("all-to-all-start", "1", "collective"),
        ("fusion.4", "1", "collective")]
    assert s.class_s("collective") == pytest.approx(7e-6)    # 2 + 4 + 1 us
    assert s.class_s("sort") == pytest.approx(3e-6)
    assert s.busy_s() == pytest.approx(5e-6)                 # (5 + 5) / 2
    assert s.window_s == pytest.approx(10e-6)


def committed(name, qid):
    """A committed v5e trace reduced with the program it ran."""
    from jax.profiler import ProfileData
    hlo, pb = (os.path.join(DATA, f"{name}.{ext}")
               for ext in ("hlo.txt", "xplane.pb"))
    if os.path.exists(hlo):
        with open(hlo) as f:
            text = f.read()
        return tr.reduce(ProfileData.from_file(pb), {qid: text})
    with gzip.open(hlo + ".gz", "rt") as f:
        text = f.read()
    with gzip.open(pb + ".gz") as f:
        profile = ProfileData.from_serialized_xspace(f.read())
    return tr.reduce(profile, {qid: text})


# op counts and device seconds by class, as the reduction read them before
# collectives had a class of their own
BEFORE = {
    "v5e_small": ({"other": 23, "gather_scatter": 3, "sort": 1},
                  {"sort": 9.3721e-05, "gather_scatter": 0.000330292,
                   "other": 8.014e-06, "kernel": 0.0}),
    "v5e_scoped": ({"other": 131, "gather_scatter": 59, "sort": 1,
                    "kernel": 1},
                   {"kernel": 1.5816e-05, "sort": 7.165e-06,
                    "gather_scatter": 0.003178445, "other": 2.8235e-05}),
}


@pytest.mark.parametrize("name,qid", [("v5e_small", 0), ("v5e_scoped", 12)])
def test_one_chip_traces_classify_as_before(name, qid):
    s = committed(name, qid)
    counts, seconds = BEFORE[name]
    assert dict(collections.Counter(o.cls for o in s.ops)) == counts
    for cls in tr.CLASSES:
        assert s.class_s(cls) == pytest.approx(seconds.get(cls, 0.0),
                                               rel=1e-12, abs=0.0)
