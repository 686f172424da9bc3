"""Q3, shipping priority."""
from . import columns, frame


def answer(data, params, ft):
    date = params["q3_date"]
    cu = frame(data, "customer", ["c_custkey", "c_mktsegment"], ft)
    cu = cu[cu.c_mktsegment == data.code("c_mktsegment", "BUILDING")]
    od = frame(data, "orders", [
        "o_orderkey", "o_custkey", "o_orderdate", "o_shippriority"], ft)
    od = od[(od.o_orderdate < date) & od.o_custkey.isin(cu.c_custkey)]
    li = frame(data, "lineitem", [
        "l_orderkey", "l_extendedprice", "l_discount", "l_shipdate"], ft)
    li = li[li.l_shipdate > date]
    j = li.merge(od, left_on="l_orderkey", right_on="o_orderkey")
    j = j.assign(rev=j.l_extendedprice * (ft(1) - j.l_discount))
    g = j.groupby(["l_orderkey", "o_orderdate", "o_shippriority"],
                  sort=False).rev.sum().rename("revenue").reset_index()
    g = g.sort_values(["revenue", "o_orderdate"], ascending=[False, True],
                      kind="stable").head(10)
    return columns(g, ["l_orderkey", "revenue", "o_orderdate",
                       "o_shippriority"])
