"""Q12, shipping modes and order priority."""
import numpy as np

from . import columns, frame, text_rank


def answer(data, params, ft):
    li = frame(data, "lineitem", [
        "l_orderkey", "l_shipmode", "l_shipdate", "l_commitdate",
        "l_receiptdate"], ft)
    modes = [data.code("l_shipmode", m) for m in ("MAIL", "SHIP")]
    li = li[li.l_shipmode.isin(modes)
            & (li.l_commitdate < li.l_receiptdate)
            & (li.l_shipdate < li.l_commitdate)
            & (li.l_receiptdate >= data.days("1994-01-01"))
            & (li.l_receiptdate < data.days("1995-01-01"))]
    od = frame(data, "orders", ["o_orderkey", "o_orderpriority"], ft)
    j = li.merge(od, left_on="l_orderkey", right_on="o_orderkey")
    high = [data.code("o_orderpriority", p) for p in ("1-URGENT", "2-HIGH")]
    j = j.assign(high=j.o_orderpriority.isin(high).astype(np.int64))
    j = j.assign(low=1 - j.high)
    g = j.groupby("l_shipmode", sort=False).agg(
        high_line_count=("high", "sum"),
        low_line_count=("low", "sum")).reset_index()
    g = g.iloc[np.argsort(text_rank(data, "l_shipmode", g.l_shipmode),
                          kind="stable")]
    return columns(g, ["l_shipmode", "high_line_count", "low_line_count"])
