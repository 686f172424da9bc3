"""Q10, returned item reporting (on the columns this schema holds)."""
from . import columns, frame


def answer(data, params, ft):
    od = frame(data, "orders", ["o_orderkey", "o_custkey", "o_orderdate"], ft)
    od = od[(od.o_orderdate >= data.days("1993-10-01"))
            & (od.o_orderdate < data.days("1994-01-01"))]
    li = frame(data, "lineitem", [
        "l_orderkey", "l_extendedprice", "l_discount", "l_returnflag"], ft)
    li = li[li.l_returnflag == data.code("l_returnflag", "R")]
    j = li.merge(od, left_on="l_orderkey", right_on="o_orderkey")
    j = j.assign(rev=j.l_extendedprice * (ft(1) - j.l_discount))
    g = j.groupby("o_custkey", sort=False).rev.sum().rename(
        "revenue").reset_index()
    cu = frame(data, "customer", ["c_custkey", "c_acctbal", "c_nationkey"],
               ft)
    g = g.merge(cu, left_on="o_custkey", right_on="c_custkey")
    g = g.sort_values("revenue", ascending=False, kind="stable").head(20)
    return columns(g, ["o_custkey", "revenue", "c_acctbal", "c_nationkey"])
