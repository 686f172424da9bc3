"""Q5, local supplier volume (the region is ASIA)."""
import numpy as np

from . import columns, frame


def answer(data, params, ft):
    region = data.code("r_name", "ASIA")
    nat = data.tables["nation"]
    asia = nat["n_nationkey"][nat["n_regionkey"] == region]
    od = frame(data, "orders", ["o_orderkey", "o_custkey", "o_orderdate"], ft)
    od = od[(od.o_orderdate >= params["q5_date_lo"])
            & (od.o_orderdate < params["q5_date_hi"])]
    cu = frame(data, "customer", ["c_custkey", "c_nationkey"], ft)
    su = frame(data, "supplier", ["s_suppkey", "s_nationkey"], ft)
    li = frame(data, "lineitem", [
        "l_orderkey", "l_suppkey", "l_extendedprice", "l_discount"], ft)
    j = li.merge(od, left_on="l_orderkey", right_on="o_orderkey")
    j = j.merge(cu, left_on="o_custkey", right_on="c_custkey")
    j = j.merge(su, left_on="l_suppkey", right_on="s_suppkey")
    j = j[np.isin(j.c_nationkey, asia) & np.isin(j.s_nationkey, asia)
          & (j.c_nationkey == j.s_nationkey)]
    j = j.assign(rev=j.l_extendedprice * (ft(1) - j.l_discount))
    g = j.groupby("s_nationkey", sort=False).rev.sum().rename(
        "revenue").reset_index()
    g = g.sort_values("revenue", ascending=False, kind="stable")
    return columns(g, ["s_nationkey", "revenue"])
