"""The data keeps dbgen's key layout and value rules."""

import numpy as np
import pytest

from bench.harness.spec import resolve


@pytest.fixture(scope="module")
def data():
    mod = resolve("isolated_c8")["data"]
    return mod, mod.generate(0.01, 2**31 + 5)


def test_order_keys_are_sparse_as_dbgen_makes_them(data):
    _, t = data
    ok = t["orders"][0]["o_orderkey"].astype(np.int64)
    assert ok[:10].tolist() == [1, 2, 3, 4, 5, 6, 7, 32, 33, 34]
    assert len(np.unique(ok)) == len(ok) and ok.max() > 3.9 * len(ok)
    assert set(np.unique(ok // 8 % 4)) == {0}  # 8 of every 32 values
    assert np.isin(t["lineitem"][0]["l_orderkey"], ok).all()


def test_prices_follow_the_part(data):
    mod, t = data
    li, pa = t["lineitem"][0], t["part"][0]
    want = li["l_quantity"] * mod.retail_price(li["l_partkey"].astype(np.int64))
    assert np.array_equal(li["l_extendedprice"], want)
    assert pa["p_retailprice"][0] == 901.0  # spec: (90000 + 0 + 100) / 100 for key 1


def test_lineitem_suppliers_are_their_parts_suppliers(data):
    _, t = data
    li, ps = t["lineitem"][0], t["partsupp"][0]
    pairs = set(zip(ps["ps_partkey"].tolist(), ps["ps_suppkey"].tolist()))
    assert len(pairs) == len(ps["ps_partkey"])
    assert all(p in pairs for p in zip(li["l_partkey"].tolist(), li["l_suppkey"].tolist()))


def test_customers_and_names(data):
    mod, t = data
    assert not (t["orders"][0]["o_custkey"] % 3 == 0).any()
    names = t["part"][1]["p_name"]
    assert len(names) == len(t["part"][0]["p_partkey"])
    for name in names[:200]:
        words = name.split()
        assert len(words) == 5 == len(set(words)) and set(words) <= set(mod.COLORS)
