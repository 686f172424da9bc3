"""Plain reference answers to the TPC-H queries, one module per query.

Each module ``qNN`` has ``answer(data, params, ft)``: the query's result as
NumPy columns, computed straight from the spec's SQL over the benchmark's
generated tables with NumPy and pandas.  ``ft`` is the float type that every
money column is cast to and every float computation runs in: float64 for the
reference, float32 for the control that the comparison must reject.
Dictionary-encoded strings are compared by their text.
"""
from __future__ import annotations

import numpy as np
import pandas as pd


def frame(data, table: str, cols: list[str], ft) -> pd.DataFrame:
    """Columns of ``table`` as a DataFrame, float columns cast to ``ft``."""
    t = data.tables[table]
    return pd.DataFrame({c: t[c].astype(ft) if t[c].dtype.kind == "f"
                         else t[c] for c in cols}, copy=False)


def codes(data, col: str, pred) -> np.ndarray:
    """Dictionary codes of ``col`` whose text satisfies ``pred``."""
    return np.nonzero([pred(s) for s in data.dicts[col]])[0]


def text_rank(data, col: str, values: np.ndarray) -> np.ndarray:
    """Rank of each code's text, for ORDER BY on a string column."""
    order = np.argsort(np.argsort(data.dicts[col]))
    return order[values]


def columns(df: pd.DataFrame, names: list[str]) -> dict[str, np.ndarray]:
    return {n: df[n].to_numpy() for n in names}
