"""TPC-H data for the benchmark, made from the run's seed.

A copy of the engine's dbgen-shaped generator with three changes that a
benchmark needs:

* Every table's row count is a function of the scale factor alone.  Lineitem
  gives each order 1 to 7 lines, as the spec does, but as a seeded
  permutation of a fixed multiset (every count equally often), so the table
  always has the same number of rows, 4 per order on average.
* The string dictionaries (comment texts, part names) are the vocabulary, not
  data: they are made from a fixed seed, as dbgen's text pool is fixed.  The
  engine embeds dictionary lookups in its programs, so a vocabulary that moved
  with the seed would compile a new program for every seed.
* Hot keys of the skewed (JCC-H-style) variant are drawn from the keys that
  exist, and the rule that one customer in three places no order is applied
  after the skew, so it holds for every skew.

The seed changes values, never shapes.  The engine sizes its programs from
row counts and from the minimum and maximum of every integer column, so each
uniform integer draw puts its range's two ends on two rows that the seed
picks, and the earliest and the latest order each get a line with the
shortest and with the longest ship, commit and receipt delays.  Every
extreme is then the same on every seed, at every scale factor.

Tables are plain NumPy columns; strings are dictionary codes into ``dicts``.
"""
from __future__ import annotations

import dataclasses

import numpy as np

REGIONS = ["AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST"]
NATIONS = [
    "ALGERIA", "ARGENTINA", "BRAZIL", "CANADA", "EGYPT", "ETHIOPIA", "FRANCE",
    "GERMANY", "INDIA", "INDONESIA", "IRAN", "IRAQ", "JAPAN", "JORDAN",
    "KENYA", "MOROCCO", "MOZAMBIQUE", "PERU", "CHINA", "ROMANIA",
    "SAUDI ARABIA", "VIETNAM", "RUSSIA", "UNITED KINGDOM", "UNITED STATES"]
NATION_REGION = [0, 1, 1, 1, 4, 0, 3, 3, 2, 2, 4, 4, 2, 4, 0,
                 0, 0, 1, 2, 3, 4, 2, 3, 3, 1]
SEGMENTS = ["AUTOMOBILE", "BUILDING", "FURNITURE", "MACHINERY", "HOUSEHOLD"]
PRIORITIES = ["1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED", "5-LOW"]
SHIPMODES = ["REG AIR", "AIR", "RAIL", "SHIP", "TRUCK", "MAIL", "FOB",
             "AIR REG"]
INSTRUCTS = ["DELIVER IN PERSON", "COLLECT COD", "NONE", "TAKE BACK RETURN"]
TYPES = [f"{a} {b} {c}"
         for a in ["STANDARD", "SMALL", "MEDIUM", "LARGE", "ECONOMY", "PROMO"]
         for b in ["ANODIZED", "BURNISHED", "PLATED", "POLISHED", "BRUSHED"]
         for c in ["TIN", "NICKEL", "BRASS", "STEEL", "COPPER"]]
CONTAINERS = [f"{a} {b}" for a in ["SM", "LG", "MED", "JUMBO"]
              for b in ["CASE", "BOX", "BAG", "JAR", "PKG", "PACK", "CAN",
                        "DRUM", "BARREL", "BOTTLE"]]
BRANDS = [f"Brand#{i}{j}" for i in range(1, 6) for j in range(1, 6)]
MFGRS = [f"Manufacturer#{i}" for i in range(1, 6)]
COLORS = """almond antique aquamarine azure beige bisque black blanched blue
blush brown burlywood burnished chartreuse chiffon chocolate coral cornflower
cornsilk cream cyan dark deep dim dodger drab firebrick floral forest frosted
gainsboro ghost goldenrod green grey honeydew hot indian ivory khaki lace
lavender lawn lemon light lime linen magenta maroon medium metallic midnight
mint misty moccasin navajo navy olive orange orchid pale papaya peach peru
pink plum powder puff purple red rose rosy royal saddle salmon sandy seashell
sienna sky slate smoke snow spring steel tan thistle tomato turquoise violet
wheat white yellow""".split()
WORDS = """carefully final deposits sleep furiously quick requests boost
blithely ironic packages cajole express accounts haggle silent pinto beans
wake regular theodolites nag slyly bold foxes integrate daring
sauternes""".split()

EPOCH = np.datetime64("1970-01-01")
CURRENT_DATE = "1995-06-17"      # the spec's "current date" for statuses
N_COMMENTS = 512                 # comment templates (assumed, see config)
VOCABULARY_SEED = 20170901       # fixed: the text pool is not data


def days(date: str) -> int:
    """ISO date -> days since 1970-01-01."""
    return int((np.datetime64(date) - EPOCH).astype(np.int64))


@dataclasses.dataclass
class Data:
    """Generated tables (NumPy columns) and string dictionaries."""
    tables: dict[str, dict[str, np.ndarray]]
    dicts: dict[str, np.ndarray]
    scale: float

    days = staticmethod(days)

    def nbytes(self) -> int:
        return sum(c.nbytes for t in self.tables.values() for c in t.values())

    def column_bytes(self) -> dict[str, int]:
        return {name: c.nbytes for t in self.tables.values()
                for name, c in t.items()}

    def code(self, col: str, value: str) -> int:
        """Dictionary code of one string value of ``col``."""
        hit = np.nonzero(self.dicts[col] == value)[0]
        if hit.size == 0:
            raise KeyError(f"{value!r} is not in the dictionary of {col}")
        return int(hit[0])


def row_counts(scale: float) -> dict[str, int]:
    """Rows per table: a function of the scale factor alone."""
    n_ord = max(96, int(1_500_000 * scale))
    n_part = max(64, int(200_000 * scale))
    return {"region": 5, "nation": 25,
            "supplier": max(16, int(10_000 * scale)),
            "customer": max(48, int(150_000 * scale)),
            "part": n_part, "partsupp": 4 * n_part,
            "orders": n_ord, "lineitem": int(_lines_per_order(n_ord).sum())}


def _lines_per_order(n_ord: int) -> np.ndarray:
    """The fixed multiset of lines per order: 1..7, each equally often."""
    return (np.arange(n_ord, dtype=np.int64) % 7) + 1


def _vocabulary(n_part: int) -> dict[str, np.ndarray]:
    rng = np.random.default_rng(VOCABULARY_SEED)

    def comments(specials: tuple[str, str], share: float) -> np.ndarray:
        base = [" ".join(rng.choice(WORDS, size=8)) for _ in range(N_COMMENTS)]
        for i in range(max(1, int(N_COMMENTS * share))):
            mid = " ".join(rng.choice(WORDS, size=2))
            base[i] = (f"{base[i][:20]} {specials[0]}{mid}{specials[1]} "
                       f"{base[i][20:40]}")
        return np.array(base)

    n_names = min(2048, max(64, n_part // 4))
    return {
        "r_name": np.array(REGIONS), "n_name": np.array(NATIONS),
        "c_mktsegment": np.array(SEGMENTS),
        "o_orderpriority": np.array(PRIORITIES),
        "l_shipmode": np.array(SHIPMODES),
        "l_shipinstruct": np.array(INSTRUCTS),
        "o_orderstatus": np.array(["F", "O", "P"]),
        "l_returnflag": np.array(["A", "N", "R"]),
        "l_linestatus": np.array(["F", "O"]),
        "p_type": np.array(TYPES), "p_container": np.array(CONTAINERS),
        "p_brand": np.array(BRANDS), "p_mfgr": np.array(MFGRS),
        "o_comment": comments(("special", "requests"), 32 / 512),
        "s_comment": comments(("Customer", "Complaints"), 16 / 512),
        "p_name": np.array([" ".join(rng.choice(COLORS, size=5,
                                                replace=False))
                            for _ in range(n_names)]),
    }


def generate(scale: float, seed: int, skew: float = 0.0,
             hot_share: float = 0.005) -> Data:
    """TPC-H at ``scale`` from ``seed``.

    ``skew`` is the share of ``o_custkey`` and ``l_partkey`` draws that go to
    a hot population of ``hot_share`` of the keys (JCC-H-style; 0 = TPC-H's
    uniform keys).
    """
    rng = np.random.default_rng(np.random.SeedSequence([seed, 0]))
    counts = row_counts(scale)
    n_part, n_supp = counts["part"], counts["supplier"]
    n_cust, n_ord = counts["customer"], counts["orders"]
    dicts = _vocabulary(n_part)

    def pinned(draw: np.ndarray, lo: int, hi: int) -> np.ndarray:
        """``draw`` with ``lo`` and ``hi`` on two rows the seed picks."""
        at = rng.choice(draw.size, 2, replace=False)
        draw[at] = lo, hi
        return draw

    def ints(lo: int, hi: int, n: int, dtype=np.int64) -> np.ndarray:
        """Uniform integers in [lo, hi], both ends present."""
        return pinned(rng.integers(lo, hi + 1, n).astype(dtype), lo, hi)

    def skewed(n_keys: int, draw: np.ndarray) -> np.ndarray:
        """Redirect a ``skew`` share of 1-based key draws to hot keys."""
        if skew <= 0:
            return draw
        hot = rng.integers(1, n_keys + 1, max(1, int(n_keys * hot_share)))
        take = rng.random(draw.size) < skew
        out = draw.copy()
        out[take] = hot[rng.integers(0, hot.size, int(take.sum()))]
        return out

    region = {"r_regionkey": np.arange(5, dtype=np.int64),
              "r_name": np.arange(5, dtype=np.int32)}
    nation = {"n_nationkey": np.arange(25, dtype=np.int64),
              "n_name": np.arange(25, dtype=np.int32),
              "n_regionkey": np.array(NATION_REGION, dtype=np.int64)}
    supplier = {
        "s_suppkey": np.arange(1, n_supp + 1, dtype=np.int64),
        "s_nationkey": ints(0, 24, n_supp),
        "s_acctbal": np.round(rng.uniform(-999.99, 9999.99, n_supp), 2),
        "s_comment": ints(0, N_COMMENTS - 1, n_supp, np.int32),
    }
    customer = {
        "c_custkey": np.arange(1, n_cust + 1, dtype=np.int64),
        "c_nationkey": ints(0, 24, n_cust),
        "c_acctbal": np.round(rng.uniform(-999.99, 9999.99, n_cust), 2),
        "c_mktsegment": ints(0, 4, n_cust, np.int32),
    }
    customer["c_phone_cc"] = customer["c_nationkey"] + 10
    part = {
        "p_partkey": np.arange(1, n_part + 1, dtype=np.int64),
        "p_name": ints(0, len(dicts["p_name"]) - 1, n_part, np.int32),
        "p_brand": ints(0, 24, n_part, np.int32),
        "p_type": ints(0, len(TYPES) - 1, n_part, np.int32),
        "p_size": ints(1, 50, n_part),
        "p_container": ints(0, len(CONTAINERS) - 1, n_part,
                            np.int32),
    }
    part["p_mfgr"] = part["p_brand"] // 5
    retail = (90000 + (part["p_partkey"] % 20001)
              + 100 * (part["p_partkey"] % 1000)) / 100.0

    # partsupp: the spec's four suppliers per part, which cover every
    # (l_partkey, l_suppkey) pair drawn below
    pk = np.repeat(part["p_partkey"], 4)
    sk = (pk + np.tile(np.arange(4, dtype=np.int64), n_part)
          * (n_supp // 4 + (pk - 1) // n_supp)) % n_supp + 1
    if np.unique(pk * (n_supp + 1) + sk).size != pk.size:
        raise ValueError(f"scale {scale} is too small: partsupp's spec "
                         "formula repeats (partkey, suppkey) pairs")
    partsupp = {
        "ps_partkey": pk, "ps_suppkey": sk,
        "ps_availqty": ints(1, 9999, pk.size),
        "ps_supplycost": np.round(rng.uniform(1.0, 1000.0, pk.size), 2),
    }

    # orders: one customer in three (custkey divisible by 3) never orders
    ck = skewed(n_cust, rng.integers(1, n_cust + 1, n_ord).astype(np.int64))
    ck = pinned(np.where(ck % 3 == 0, ck - 1, ck), 1,
                n_cust - (n_cust % 3 == 0))
    odate = ints(days("1992-01-01"), days("1998-08-02"), n_ord)
    orders = {
        "o_orderkey": np.arange(1, n_ord + 1, dtype=np.int64),
        "o_custkey": ck,
        "o_orderdate": odate,
        "o_orderpriority": ints(0, 4, n_ord, np.int32),
        "o_shippriority": np.zeros(n_ord, dtype=np.int64),
        "o_comment": ints(0, N_COMMENTS - 1, n_ord, np.int32),
    }

    per = rng.permutation(_lines_per_order(n_ord))
    n_li = int(per.sum())
    lok = np.repeat(orders["o_orderkey"], per)
    lod = np.repeat(odate, per)
    lpk = pinned(skewed(n_part, rng.integers(1, n_part + 1, n_li)), 1, n_part)
    isup = rng.integers(0, 4, n_li)
    lsk = (lpk + isup * (n_supp // 4 + (lpk - 1) // n_supp)) % n_supp + 1
    qty = ints(1, 50, n_li)
    eprice = np.round(qty * retail[lpk - 1], 2)
    starts = np.cumsum(per) - per
    ends = starts[[np.argmin(odate), np.argmax(odate)]]
    ship = lod + rng.integers(1, 122, n_li)
    commit = lod + rng.integers(30, 91, n_li)
    receipt = ship + rng.integers(1, 31, n_li)
    ship[ends] = lod[ends] + (1, 121)
    commit[ends] = lod[ends] + (30, 90)
    receipt[ends] = ship[ends] + (1, 30)
    cur = days(CURRENT_DATE)
    lstat = (ship > cur).astype(np.int32)              # 0 = F, 1 = O
    rflag = np.where(receipt <= cur, rng.integers(0, 2, n_li) * 2,
                     1).astype(np.int32)               # A / R, else N
    lineitem = {
        "l_orderkey": lok,
        "l_partkey": lpk,
        "l_suppkey": lsk,
        "l_linenumber": (np.arange(n_li, dtype=np.int64)
                         - np.repeat(starts, per) + 1),
        "l_quantity": qty,
        "l_extendedprice": eprice,
        "l_discount": np.round(rng.uniform(0.0, 0.10, n_li), 2),
        "l_tax": np.round(rng.uniform(0.0, 0.08, n_li), 2),
        "l_returnflag": rflag,
        "l_linestatus": lstat,
        "l_shipdate": ship,
        "l_commitdate": commit,
        "l_receiptdate": receipt,
        "l_shipinstruct": ints(0, 3, n_li, np.int32),
        "l_shipmode": ints(0, len(SHIPMODES) - 1, n_li, np.int32),
    }

    # o_totalprice is the sum of the order's charges; o_orderstatus is F when
    # every line has shipped, O when none has, P otherwise
    charge = eprice * (1 + lineitem["l_tax"]) * (1 - lineitem["l_discount"])
    orders["o_totalprice"] = np.round(
        np.bincount(lok - 1, weights=charge, minlength=n_ord), 2)
    n_open = np.bincount(lok - 1, weights=lstat, minlength=n_ord)
    orders["o_orderstatus"] = np.where(
        n_open == 0, 0, np.where(n_open == per, 1, 2)).astype(np.int32)

    tables = {"region": region, "nation": nation, "supplier": supplier,
              "customer": customer, "part": part, "partsupp": partsupp,
              "orders": orders, "lineitem": lineitem}
    for name, n in counts.items():
        got = len(next(iter(tables[name].values())))
        if got != n:
            raise AssertionError(f"{name}: {got} rows, expected {n}")
    return Data(tables, dicts, scale)
