"""Q14, promotion effect."""
import numpy as np

from . import codes, frame


def answer(data, params, ft):
    li = frame(data, "lineitem", [
        "l_partkey", "l_extendedprice", "l_discount", "l_shipdate"], ft)
    li = li[(li.l_shipdate >= data.days("1995-09-01"))
            & (li.l_shipdate < data.days("1995-10-01"))]
    pa = frame(data, "part", ["p_partkey", "p_type"], ft)
    j = li.merge(pa, left_on="l_partkey", right_on="p_partkey")
    rev = (j.l_extendedprice * (ft(1) - j.l_discount)).to_numpy()
    promo = np.isin(j.p_type.to_numpy(),
                    codes(data, "p_type", lambda s: s.startswith("PROMO")))
    share = (ft(100) * np.where(promo, rev, ft(0)).sum(dtype=ft)
             / rev.sum(dtype=ft))
    return {"promo_revenue": np.array([share], dtype=ft)}
