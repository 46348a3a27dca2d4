"""BitWeaving/V range scan ``lo <= column < hi``: a ``greater_equal``, a
``greater`` and an ``and_red`` per chain, as ``apps/bitweaving.run``
builds it.  The selectivity is drawn per query from ``selectivity`` and
placed at a random position of the column's distribution."""

import numpy as np

from bench import reference
from bench.generator import Query, QueueBuilder, Table, assemble, rng_for


class RangeScan(Table):
    checks = ("sel_wrong",)

    def __init__(self, config, mix, seed):
        super().__init__(config, mix, seed)
        self.column = mix["column"]
        counts = np.bincount(self.columns[self.column],
                             minlength=1 << self.bits[self.column])
        self.below = np.concatenate([[0], np.cumsum(counts)]) / self.rows

    def make(self, stream, k):
        rng = rng_for(self.seed, stream, k)
        s_lo, s_hi = self.mix["selectivity"]
        share = float(rng.uniform(s_lo, s_hi))
        start = float(rng.uniform(0.0, 1.0 - share))
        top = reference.mask(self.bits[self.column])
        lo = min(int(np.searchsorted(self.below, start)), top)
        hi = min(int(np.searchsorted(self.below, start + share)), top)
        col, bits = self.columns[self.column], self.bits[self.column]
        qb = QueueBuilder()
        out = {"sel": []}
        for sl in self.shards:
            x = col[sl]
            n = x.shape[0]
            ge = qb.emit("greater_equal", x, np.full(n, lo, np.int64),
                         n_bits=bits)
            lt = qb.emit("greater", np.full(n, hi, np.int64), x,
                         n_bits=bits)
            ones = np.ones(n, np.int64)
            sel = qb.emit("and_red", ge, lt, ones, ones, n_bits=1)
            out["sel"].append((sl, sel.producer, 0))
        return Query(stream, k, {"lo": lo, "hi": hi}, qb.instrs, out,
                     qb.n_bytes)

    def collect(self, q, results):
        return {"sel": assemble(results, q.out["sel"], self.rows,
                                np.uint8)}

    def check(self, q, got):
        want = reference.range_scan(self.columns[self.column],
                                    q.params["lo"], q.params["hi"])
        return {"sel_wrong": reference.lanes_wrong(got.get("sel"), want)}


FAMILY = RangeScan
