"""TPC-H Q6 with the specification's substitution parameters drawn per
query: DATE is 1 January of a year in ``years``, DISCOUNT is drawn from
``discount`` (hundredths) and the band is DISCOUNT ± ``discount_band``,
QUANTITY is drawn from ``quantity``.  One chain per compute unit: five
comparisons, two ``and_red``, the multiplication of price by discount at
the price's width and the predicating ``if_else`` at the product's; the
SUM is taken on the host.  The chain is ``apps/tpch.q6_queue``'s, with
per-query parameters and the configuration's widths."""

import datetime

import numpy as np

from bench import reference
from bench.generator import (Query, QueueBuilder, Table, assemble,
                             dtype_for, rng_for)


class TpchQ6(Table):
    checks = ("sel_wrong", "revenue_wrong", "sum_wrong")

    def make(self, stream, k):
        m = self.mix
        rng = rng_for(self.seed, stream, k)
        year = int(rng.integers(m["years"][0], m["years"][1] + 1))
        disc = int(rng.integers(m["discount"][0], m["discount"][1] + 1))
        params = {
            "date_lo": self.day("l_shipdate", datetime.date(year, 1, 1)),
            "date_hi": self.day("l_shipdate", datetime.date(year + 1, 1, 1)),
            "disc_lo": disc - m["discount_band"],
            "disc_hi": disc + m["discount_band"],
            "qty_lt": int(rng.integers(m["quantity"][0],
                                       m["quantity"][1] + 1)),
        }
        c, b = self.columns, self.bits
        qb = QueueBuilder()
        out = {"sel": [], "revenue": []}
        for sl in self.shards:
            sd, dc = c["l_shipdate"][sl], c["l_discount"][sl]
            qt, pr = c["l_quantity"][sl], c["l_extendedprice"][sl]
            n = sd.shape[0]

            def const(v):
                return np.full(n, v, np.int64)

            t_lo = qb.emit("greater_equal", sd, const(params["date_lo"]),
                           n_bits=b["l_shipdate"])
            t_hi = qb.emit("greater", const(params["date_hi"]), sd,
                           n_bits=b["l_shipdate"])
            d_lo = qb.emit("greater_equal", dc, const(params["disc_lo"]),
                           n_bits=b["l_discount"])
            d_hi = qb.emit("greater_equal", const(params["disc_hi"]), dc,
                           n_bits=b["l_discount"])
            q_lt = qb.emit("greater", const(params["qty_lt"]), qt,
                           n_bits=b["l_quantity"])
            both = qb.emit("and_red", t_lo, t_hi, d_lo, d_hi, n_bits=1)
            sel = qb.emit("and_red", both, q_lt, const(1), const(1),
                          n_bits=1)
            prod = qb.emit("multiplication", pr, dc,
                           n_bits=b["l_extendedprice"])
            rev = qb.emit("if_else", sel, prod, const(0),
                          n_bits=2 * b["l_extendedprice"])
            out["sel"].append((sl, sel.producer, 0))
            out["revenue"].append((sl, rev.producer, 0))
        return Query(stream, k, params, qb.instrs, out, qb.n_bytes)

    def collect(self, q, results):
        return {
            "sel": assemble(results, q.out["sel"], self.rows, np.uint8),
            "revenue": assemble(
                results, q.out["revenue"], self.rows,
                dtype_for(2 * self.bits["l_extendedprice"])),
        }

    def check(self, q, got):
        c = self.columns
        sel, rev, total = reference.tpch_q6(
            c["l_shipdate"], c["l_discount"], c["l_quantity"],
            c["l_extendedprice"], q.params)
        got_rev = got.get("revenue")
        got_total = (int(np.asarray(got_rev, np.int64).sum())
                     if got_rev is not None else None)
        return {"sel_wrong": reference.lanes_wrong(got.get("sel"), sel),
                "revenue_wrong": reference.lanes_wrong(got_rev, rev),
                "sum_wrong": int(got_total != total)}


FAMILY = TpchQ6
