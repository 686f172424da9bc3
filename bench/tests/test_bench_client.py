"""Set-up compiles in the cell's order, garbage collections in the window
are counted, and the check names where its largest error lies."""
import gc

import numpy as np

import benchkit  # noqa: F401  (puts bench/ on the path)
from harness import check
from harness.client import Client, Execution, GcPauses


def test_set_up_compiles_in_the_cells_order():
    class Server:
        order = []

        def compiled(self, qid):
            self.order.append(qid)

    client = Client.__new__(Client)
    client.server = Server()
    client.prepare((14, 6, 1, 19, 12))
    assert Server.order == [14, 6, 1, 19, 12]


def test_a_collection_in_the_block_is_counted():
    with GcPauses() as pauses:
        gc.collect()
    assert len(pauses.pauses) == 1 and pauses.pauses[0] >= 0.0
    gc.collect()                     # outside the block: not counted
    assert len(pauses.pauses) == 1


def test_the_check_names_the_query_and_column_of_its_worst_error(
        monkeypatch):
    want = {6: {"revenue": np.array([100.0])},
            1: {"flag": np.array([1, 2]), "sum_qty": np.array([10.0, 20.0]),
                "avg": np.array([4.0, 8.0])}}
    monkeypatch.setattr(check, "reference",
                        lambda name, qid: lambda data, params, ft: want[qid])
    got = {6: {"revenue": np.array([100.0 * (1 + 1e-12)])},
           1: {"flag": np.array([1, 2]), "sum_qty": np.array([10.0, 20.0]),
               "avg": np.array([4.0, 8.0 * (1 + 1e-9)])}}
    runs = [Execution(q, {}, 0.0, 0.0, got[q]) for q in (6, 1)]
    limits = {"answers_wrong": 0, "max_rel_err": 1e-10}
    numbers, where = check.check(runs, None, "tpch", limits)
    assert where == "q1 avg"
    assert numbers["answers_wrong"]["value"] == 0
    assert 0.9e-9 < numbers["max_rel_err"]["value"] < 1.1e-9
    assert not check.passed(numbers)
