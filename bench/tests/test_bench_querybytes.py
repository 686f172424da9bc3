"""querybytes.json: the columns each query reads, from the spec's SQL."""
import json
import os
import re

import pytest

from benchkit import BENCH, ROOT
from harness import datagen

QB = json.load(open(os.path.join(BENCH, "querybytes.json")))["queries"]
SCHEMA = {c for t in datagen.generate(0.01, 1).tables.values() for c in t}


def test_all_22_queries_are_listed():
    assert sorted(map(int, QB)) == list(range(1, 23))


@pytest.mark.parametrize("qid", range(1, 23))
def test_columns_match_the_sql_up_to_the_listed_differences(qid):
    e = QB[str(qid)]
    assert set(e["columns"]) <= SCHEMA
    assert not set(e.get("not_in_schema", [])) & SCHEMA
    with open(os.path.join(ROOT, "src", "repro", "queries", "sql",
                           f"q{qid}.sql")) as f:
        sql = f.read()
    refs = {w for w in re.findall(r"\b[a-z]+_[a-z_]+\b", sql) if w in SCHEMA}
    expected = (set(e["columns"]) - set(e.get("sql_lacks", []))
                | set(e.get("sql_adds", [])))
    assert refs == expected
