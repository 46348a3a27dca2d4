"""Each mix's reference agrees with the program's sequential backend
(``SimdramDevice(backend="bitplane")``) at a small size."""

import numpy as np
import pytest

from bench import generator, reference

from conftest import CELLS, small_spec


def _bitplane():
    from repro.core.isa import SimdramDevice
    return SimdramDevice(backend="bitplane")


@pytest.mark.parametrize("cell", CELLS)
def test_reference_agrees_with_bitplane(cell):
    spec = small_spec(cell, rows=4096, lanes=512)
    fam = generator.family(spec["config"], spec["mix"], 2 ** 31 + 11)
    dev = _bitplane()
    queries = fam.warmup() + [fam.query(k) for k in range(2 * fam.block)]
    for q in queries:
        got = fam.collect(q, dev.dispatch(q.instrs))
        assert all(v == 0 for v in fam.check(q, got).values()), q.params


def test_division_by_zero_follows_the_documented_semantics():
    x = np.array([7, 65535, 0])
    q, r = reference.evaluate("division", 16, x, np.zeros(3, np.int64))
    assert q.tolist() == [65535] * 3 and r.tolist() == x.tolist()
    got = _bitplane().bbop("division", x, np.zeros(3, np.int64), n_bits=16)
    assert [g.tolist() for g in got] == [q.tolist(), r.tolist()]


def test_q6_reference_follows_the_query_text():
    sd = np.array([364, 365, 729, 730, 500])
    dc = np.array([5, 5, 5, 5, 7])
    qt = np.array([1, 23, 24, 1, 1])
    pr = np.array([100, 200, 300, 400, 500])
    params = {"date_lo": 365, "date_hi": 730, "disc_lo": 4, "disc_hi": 6,
              "qty_lt": 24}
    sel, rev, total = reference.tpch_q6(sd, dc, qt, pr, params)
    assert sel.tolist() == [False, True, False, False, False]
    assert rev.tolist() == [0, 1000, 0, 0, 0] and total == 1000


def test_the_seed_fixes_the_data_and_not_the_mix():
    spec = small_spec("ops16_bulk", lanes=64)
    a, b, again = (generator.family(spec["config"], spec["mix"], seed)
                   .query(0) for seed in (5, 6, 5))

    def shape(q):
        return [(i.op, i.n_bits, np.asarray(i.operands[0]).shape)
                for i in q.instrs]

    assert shape(a) == shape(b) and a.n_bytes == b.n_bytes
    assert {i.op for i in a.instrs} == set(spec["config"]["ops"])
    data = [[np.asarray(o) for o in q.instrs[0].operands]
            for q in (a, b, again)]
    assert all((x == y).all() for x, y in zip(data[0], data[2]))
    assert any((x != y).any() for x, y in zip(data[0], data[1]))


def test_extendedprice_follows_the_spec():
    spec = small_spec("q6_sf1", rows=50000)
    fam = generator.family(spec["config"], spec["mix"], 2 ** 31 + 5)
    price, qty = fam.columns["l_extendedprice"], fam.columns["l_quantity"]
    retail = price // qty
    assert (price == qty * retail).all()
    assert retail.min() >= 90100 and retail.max() <= 209899
    assert price.max() < 1 << 24 and price.max() > 1 << 23
