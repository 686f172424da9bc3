"""Q6, forecasting revenue change."""
import numpy as np

from . import frame


def answer(data, params, ft):
    li = frame(data, "lineitem", [
        "l_extendedprice", "l_discount", "l_quantity", "l_shipdate"], ft)
    keep = ((li.l_shipdate >= params["q6_date_lo"])
            & (li.l_shipdate < params["q6_date_hi"])
            & (li.l_discount >= ft(params["q6_disc_lo"]))
            & (li.l_discount <= ft(params["q6_disc_hi"]))
            & (li.l_quantity < params["q6_qty"]))
    li = li[keep]
    rev = (li.l_extendedprice * li.l_discount).to_numpy().sum(dtype=ft)
    return {"revenue": np.array([rev], dtype=ft)}
