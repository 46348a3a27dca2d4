"""Bulk operations: each query runs every operation of the
configuration over ``elements`` lanes, split into one contiguous shard
per compute unit, with operands drawn uniformly over their widths.  The
queue is operation-major, so each bank gets one shard of every
operation.  Every query holds the same operations over the same number
of lanes; the seed draws only the data."""

import numpy as np

from bench import reference
from bench.generator import (Family, Query, QueueBuilder, assemble,
                             dtype_for, rng_for, shard_slices)


class OpBulk(Family):
    checks = ("out_wrong",)

    def __init__(self, config, mix, seed):
        super().__init__(config, mix, seed)
        self.n_bits = int(config["n_bits"])
        self.ops = list(config["ops"])
        self.lanes = int(mix["elements"])
        self.shards = shard_slices(self.lanes, self.units)

    def operands(self, stream, k, i):
        """The operands of the query's ``i``-th operation."""
        rng = rng_for(self.seed, stream, k, i)
        in_w, _ = reference.widths(self.ops[i], self.n_bits)
        return [rng.integers(0, 1 << w, size=self.lanes, dtype=np.int64)
                for w in in_w]

    def make(self, stream, k):
        qb = QueueBuilder()
        out = {}
        for i, op in enumerate(self.ops):
            xs = self.operands(stream, k, i)
            _, out_w = reference.widths(op, self.n_bits)
            parts = [[] for _ in out_w]
            for sl in self.shards:
                ref = qb.emit(op, *(x[sl] for x in xs), n_bits=self.n_bits)
                for j, p in enumerate(parts):
                    p.append((sl, ref.producer, j))
            for j, p in enumerate(parts):
                out[f"{op}.{j}"] = p
        return Query(stream, k, {}, qb.instrs, out, qb.n_bytes)

    def collect(self, q, results):
        got = {}
        for op in self.ops:
            _, out_w = reference.widths(op, self.n_bits)
            for j, w in enumerate(out_w):
                got[f"{op}.{j}"] = assemble(results, q.out[f"{op}.{j}"],
                                            self.lanes, dtype_for(w))
        return got

    def check(self, q, got):
        wrong = 0
        for i, op in enumerate(self.ops):
            want = reference.evaluate(op, self.n_bits,
                                      *self.operands(q.stream, q.index, i))
            wrong += reference.outputs_wrong(
                [got.get(f"{op}.{j}") for j in range(len(want))], want)
        return {"out_wrong": wrong}


FAMILY = OpBulk
