"""Q1, pricing summary report."""
import numpy as np

from . import columns, frame, text_rank


def answer(data, params, ft):
    li = frame(data, "lineitem", [
        "l_returnflag", "l_linestatus", "l_quantity", "l_extendedprice",
        "l_discount", "l_tax", "l_shipdate"], ft)
    li = li[li.l_shipdate <= params["q1_cutoff"]]
    one = ft(1)
    li = li.assign(disc_price=li.l_extendedprice * (one - li.l_discount))
    li = li.assign(charge=li.disc_price * (one + li.l_tax))
    g = li.groupby(["l_returnflag", "l_linestatus"], sort=False).agg(
        sum_qty=("l_quantity", "sum"),
        sum_base_price=("l_extendedprice", "sum"),
        sum_disc_price=("disc_price", "sum"),
        sum_charge=("charge", "sum"),
        sum_disc=("l_discount", "sum"),
        count_order=("l_quantity", "size")).reset_index()
    n = g.count_order.to_numpy().astype(ft)
    g = g.assign(avg_qty=g.sum_qty.to_numpy().astype(ft) / n,
                 avg_price=g.sum_base_price.to_numpy() / n,
                 avg_disc=g.sum_disc.to_numpy() / n)
    order = np.lexsort((text_rank(data, "l_linestatus", g.l_linestatus),
                        text_rank(data, "l_returnflag", g.l_returnflag)))
    return columns(g.iloc[order], [
        "l_returnflag", "l_linestatus", "sum_qty", "sum_base_price",
        "sum_disc_price", "sum_charge", "avg_qty", "avg_price", "avg_disc",
        "count_order"])
