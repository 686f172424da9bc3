"""The plain references agree with the engine on the CPU at sf 0.02, and the
float32 control departs from them by more than rounding of float64."""
import numpy as np
import pytest

from benchkit import BENCH
from harness import check, datagen
from harness.traffic import WINDOW_STREAM, Traffic

QIDS = [1, 3, 5, 6, 10, 12, 14, 18, 19]


@pytest.fixture(scope="module")
def engine():
    from repro.core.table import Database
    from repro.serve.server import QueryServer
    data = datagen.generate(0.02, 2**31 + 99)
    return data, QueryServer(Database(data.tables, data.dicts, data.scale))


@pytest.fixture(scope="module")
def params():
    import os
    out = {}
    for name in ("join", "scan_agg"):
        t = Traffic.load(os.path.join(BENCH, "traffic", f"{name}.json"))
        for req in next(t.passes(5, WINDOW_STREAM)):
            out[req.qid] = req.params
    return out


@pytest.mark.parametrize("qid", QIDS)
def test_reference_agrees_with_the_engine(engine, params, qid):
    data, server = engine
    p = params.get(qid, {})
    got = server.submit(qid, p)
    want = check.reference("tpch", qid)(data, p, np.float64)
    assert len(next(iter(want.values()))) > 0
    ok, err, _ = check.compare(got, want)
    assert ok and err < 1e-12
    low = check.reference("tpch", qid)(data, p, np.float32)
    ok32, err32, _ = check.compare(low, want)
    floats = any(v.dtype.kind == "f" and np.any(v != 0)
                 for v in want.values())
    assert not floats or not ok32 or err32 > 1e-9
