"""The traffic generator: stream-0 order, §2.4 bindings inside the spec's
ranges and the templates' domains."""
import json
import os

import pytest

from benchkit import BENCH
from harness.datagen import days
from harness.traffic import WARM_STREAM, WINDOW_STREAM, Traffic

STREAM0 = [14, 2, 9, 20, 6, 17, 18, 8, 21, 13, 3, 22, 16, 4, 11, 15, 1, 10,
           19, 5, 7, 12]
NAMES = sorted(f[:-5] for f in os.listdir(os.path.join(BENCH, "traffic")))


def load(name):
    return Traffic.load(os.path.join(BENCH, "traffic", f"{name}.json"))


@pytest.mark.parametrize("name", NAMES)
def test_passes_follow_power_test_stream_0(name):
    t = load(name)
    assert list(t.queries) == [q for q in STREAM0 if q in t.queries]


def in_spec(qid, p):
    """The spec's section 2.4 ranges (as far as the templates admit)."""
    if qid == 1:
        return days("1998-08-03") <= p["q1_cutoff"] <= days("1998-10-01")
    if qid == 3:
        return days("1995-03-01") <= p["q3_date"] <= days("1995-03-31")
    if qid in (5, 6):
        pre = f"q{qid}_"
        years = {days(f"{y}-01-01"): y for y in range(1993, 1999)}
        lo, hi = years.get(p[pre + "date_lo"]), years.get(p[pre + "date_hi"])
        ok = lo is not None and hi == lo + 1 and 1993 <= lo <= 1997
        if qid == 6:
            d = round((p["q6_disc_lo"] + p["q6_disc_hi"]) / 2, 2)
            ok &= (0.02 <= d <= 0.08
                   and p["q6_disc_lo"] == round(d - 0.01, 2)
                   and p["q6_disc_hi"] == round(d + 0.01, 2)
                   and p["q6_qty"] in (24, 25))
        return ok
    return p == {}


@pytest.mark.parametrize("name", NAMES)
def test_bindings_fall_in_the_spec_ranges_and_template_domains(name):
    from repro.serve.templates import TEMPLATES, resolve_bindings
    t = load(name)
    seen: dict[int, set] = {}
    passes = t.passes(2**32 + 5, WINDOW_STREAM)
    for _ in range(300):
        for req in next(passes):
            assert in_spec(req.qid, req.params), (req.qid, req.params)
            resolve_bindings(TEMPLATES[req.qid].params, req.params)
            seen.setdefault(req.qid, set()).add(
                tuple(sorted(req.params.items())))
    for qid in t.rules:                   # every execution draws afresh
        assert len(seen[qid]) >= 5


def test_seeds_and_streams_repeat_and_differ():
    t = load("scan_agg")
    a = [next(t.passes(7, WINDOW_STREAM)) for _ in range(3)]
    assert a == [next(t.passes(7, WINDOW_STREAM)) for _ in range(3)]
    assert next(t.passes(7, WINDOW_STREAM)) != next(t.passes(8, WINDOW_STREAM))
    assert next(t.passes(7, WINDOW_STREAM)) != next(t.passes(7, WARM_STREAM))


def test_a_rule_of_unknown_kind_is_refused(tmp_path):
    path = tmp_path / "bad.json"
    path.write_text(json.dumps({"queries": [6], "bindings": {"6": {
        "draw": {"x": [1, 2]}, "params": {"q6_qty": ["cube", "x", 0]}}}}))
    with pytest.raises(ValueError, match="unknown kind"):
        Traffic.load(str(path))
