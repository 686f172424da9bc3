"""Q18, large volume customer (on the columns this schema holds)."""
from . import columns, frame


def answer(data, params, ft):
    li = frame(data, "lineitem", ["l_orderkey", "l_quantity"], ft)
    g = li.groupby("l_orderkey", sort=False).l_quantity.sum().rename(
        "sum_qty").reset_index()
    g = g[g.sum_qty > 300]
    od = frame(data, "orders", [
        "o_orderkey", "o_custkey", "o_orderdate", "o_totalprice"], ft)
    j = g.merge(od, left_on="l_orderkey", right_on="o_orderkey")
    cu = frame(data, "customer", ["c_custkey"], ft)
    j = j.merge(cu, left_on="o_custkey", right_on="c_custkey")
    j = j.sort_values(["o_totalprice", "o_orderdate"], ascending=[False, True],
                      kind="stable").head(100)
    return columns(j, ["l_orderkey", "sum_qty", "o_custkey", "o_orderdate",
                       "o_totalprice"])
