"""The benchmark's TPC-H generator: shapes fixed by sf, values by the seed."""
import numpy as np
import pytest

import benchkit  # noqa: F401 (puts bench/ and src/ on the path)
from harness import datagen

SF = 0.01
SEEDS = (3, 2**31 + 17)


@pytest.fixture(scope="module")
def pair():
    return [datagen.generate(SF, s) for s in SEEDS]


def rows(data):
    return {t: len(next(iter(c.values()))) for t, c in data.tables.items()}


def test_row_counts_are_a_function_of_sf(pair):
    a, b = pair
    assert rows(a) == rows(b) == datagen.row_counts(SF)
    assert abs(rows(a)["lineitem"] / rows(a)["orders"] - 4) < 0.01


def test_values_change_with_the_seed_and_repeat_with_it(pair):
    a, b = pair
    assert not np.array_equal(a.tables["lineitem"]["l_extendedprice"],
                              b.tables["lineitem"]["l_extendedprice"])
    again = datagen.generate(SF, SEEDS[0])
    for t, cols in a.tables.items():
        for c, v in cols.items():
            np.testing.assert_array_equal(v, again.tables[t][c])


def test_vocabulary_does_not_move_with_the_seed(pair):
    a, b = pair
    assert a.dicts.keys() == b.dicts.keys()
    for k in a.dicts:
        np.testing.assert_array_equal(a.dicts[k], b.dicts[k])


@pytest.mark.parametrize("skew", [0.0, 0.25])
def test_integer_extremes_are_the_same_on_every_seed(skew):
    """The engine sizes programs from integer columns' minima and maxima."""
    def extremes(d):
        return {c: (v.min(), v.max()) for t in d.tables.values()
                for c, v in t.items() if v.dtype.kind in "iu"}
    first = extremes(datagen.generate(SF, 1, skew=skew))
    for seed in (2, 99, 2**33 + 1):
        assert extremes(datagen.generate(SF, seed, skew=skew)) == first


@pytest.mark.parametrize("skew", [0.0, 0.25])
def test_spec_relationships_hold(skew):
    d = datagen.generate(SF, 5, skew=skew)
    t = d.tables
    ck = t["orders"]["o_custkey"]
    assert ck.min() >= 1 and ck.max() <= len(t["customer"]["c_custkey"])
    assert not np.any(ck % 3 == 0)          # one customer in three: no order
    li = t["lineitem"]
    assert li["l_partkey"].min() >= 1
    n_supp = len(t["supplier"]["s_suppkey"])
    ps = set(zip(t["partsupp"]["ps_partkey"].tolist(),
                 t["partsupp"]["ps_suppkey"].tolist()))
    assert set(zip(li["l_partkey"].tolist(),
                   li["l_suppkey"].tolist())) <= ps
    assert li["l_suppkey"].max() <= n_supp
    assert np.all(li["l_shipdate"] < li["l_receiptdate"])
    od = dict(zip(t["orders"]["o_orderkey"].tolist(),
                  t["orders"]["o_orderdate"].tolist()))
    odate = np.array([od[k] for k in li["l_orderkey"].tolist()])
    assert np.all(li["l_shipdate"] > odate)
    assert li["l_linenumber"].min() == 1 and li["l_linenumber"].max() == 7


def test_skew_reaches_the_hot_key_share():
    def hot_share(keys, n_keys):
        counts = np.sort(np.bincount(keys))[::-1]
        return counts[:max(1, int(n_keys * 0.005))].sum() / keys.size
    uniform = datagen.generate(0.05, 8)
    skewed = datagen.generate(0.05, 8, skew=0.25)
    for table, col, n in (("orders", "o_custkey", "customer"),
                          ("lineitem", "l_partkey", "part")):
        n_keys = len(next(iter(skewed.tables[n].values())))
        assert hot_share(skewed.tables[table][col], n_keys) >= 0.25
        assert hot_share(uniform.tables[table][col], n_keys) < 0.05


def test_too_small_a_scale_is_refused():
    with pytest.raises(ValueError, match="too small"):
        datagen.generate(0.001, 1)
