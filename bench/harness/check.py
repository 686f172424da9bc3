"""The comparison that decides ``correct``.

Every answer the window produced is compared with the plain reference
(``bench/references/<reference>/qNN.py``), run after the window closed on
the same tables and parameters.  Two numbers, each with the limit the
cell's traffic file states:

* ``answers_wrong``: answers that never came (the request raised) or whose
  row count, or any exact column (keys, codes, dates, counts, integer sums),
  differs from the reference's, in order.  Limit 0.
* ``max_rel_err``: the largest relative error of any float column of the
  other answers, ``|got - want| / |want|`` (``|got - want|`` where the
  reference reads 0).
"""
from __future__ import annotations

import importlib
from typing import Callable

import numpy as np


def reference(name: str, qid: int) -> Callable:
    """``answer(data, params, ft)`` of query ``qid`` in reference ``name``."""
    return importlib.import_module(f"references.{name}.q{qid:02d}").answer


def compare(got: dict | None, want: dict) -> tuple[bool, float, str]:
    """(row count and exact columns agree, largest float relative error,
    the column it is in)."""
    if got is None:
        return False, 0.0, ""
    worst, where = 0.0, ""
    for col, w in want.items():
        g = got.get(col)
        if g is None or len(g) != len(w):
            return False, 0.0, col
        if w.dtype.kind == "f":
            g = np.asarray(g, dtype=np.float64)
            w = np.asarray(w, dtype=np.float64)
            diff = np.abs(g - w)
            rel = np.where(w == 0, diff, diff / np.where(w == 0, 1, np.abs(w)))
            rel = np.where(np.isfinite(rel), rel, np.inf)
            err = float(rel.max(initial=0.0))
            if err > worst:
                worst, where = err, col
        elif not np.array_equal(np.asarray(g), w):
            return False, 0.0, col
    return True, worst, where


def check(executions, data, ref_name: str, limits: dict,
          answers: Callable | None = None) -> tuple[dict, str]:
    """Numbers compared, each ``{"value": v, "limit": l}``, and where the
    largest relative error lies (``q<N> <column>``).

    ``answers(execution)`` gives the answer judged; by default what the
    program returned.  The control passes its own.
    """
    want: dict[tuple, dict] = {}
    wrong, worst, where = 0, 0.0, ""
    for ex in executions:
        key = (ex.qid, tuple(sorted(ex.params.items())))
        if key not in want:
            want[key] = reference(ref_name, ex.qid)(data, ex.params,
                                                    np.float64)
        got = ex.result if answers is None else answers(ex)
        ok, err, col = compare(got, want[key])
        if not ok:
            wrong += 1
        elif err > worst:
            worst, where = err, f"q{ex.qid} {col}"
    return ({"answers_wrong": {"value": wrong,
                               "limit": limits["answers_wrong"]},
             "max_rel_err": {"value": worst,
                             "limit": limits["max_rel_err"]}}, where)


def passed(numbers: dict) -> bool:
    return all(n["value"] <= n["limit"] for n in numbers.values())
