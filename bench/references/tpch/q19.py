"""Q19, discounted revenue."""
import numpy as np

from . import frame


def answer(data, params, ft):
    li = frame(data, "lineitem", [
        "l_partkey", "l_quantity", "l_extendedprice", "l_discount",
        "l_shipinstruct", "l_shipmode"], ft)
    li = li[(li.l_shipinstruct == data.code("l_shipinstruct",
                                            "DELIVER IN PERSON"))
            & li.l_shipmode.isin([data.code("l_shipmode", m)
                                  for m in ("AIR", "AIR REG")])]
    pa = frame(data, "part", ["p_partkey", "p_brand", "p_container",
                              "p_size"], ft)
    j = li.merge(pa, left_on="l_partkey", right_on="p_partkey")
    keep = np.zeros(len(j), dtype=bool)
    for brand, size, sizes, qty in (("Brand#12", "SM", 5, (1, 11)),
                                    ("Brand#23", "MED", 10, (10, 20)),
                                    ("Brand#34", "LG", 15, (20, 30))):
        kinds = ("CASE", "BOX", "PACK", "PKG") if size != "MED" else \
            ("BAG", "BOX", "PKG", "PACK")
        boxes = [data.code("p_container", f"{size} {k}") for k in kinds]
        keep |= ((j.p_brand == data.code("p_brand", brand))
                 & j.p_container.isin(boxes)
                 & (j.p_size >= 1) & (j.p_size <= sizes)
                 & (j.l_quantity >= qty[0]) & (j.l_quantity <= qty[1])
                 ).to_numpy()
    j = j[keep]
    rev = (j.l_extendedprice * (ft(1) - j.l_discount)).to_numpy().sum(
        dtype=ft)
    return {"revenue": np.array([rev], dtype=ft)}
