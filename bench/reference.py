"""Plain numpy references for the benchmark's query families.

Written from the operations' documented semantics and from the TPC-H
query text, independently of the code under test: nothing here imports
``repro``.  Every function takes plain integer arrays and returns plain
arrays, so the same functions judge the program and the control.
"""

from __future__ import annotations

from typing import Dict, Sequence, Tuple

import numpy as np

REDUCTIONS = {"and_red": np.bitwise_and, "or_red": np.bitwise_or,
              "xor_red": np.bitwise_xor}


def mask(n: int) -> int:
    return (1 << n) - 1


def widths(op: str, n: int) -> Tuple[Tuple[int, ...], Tuple[int, ...]]:
    """``(operand widths, output widths)`` of ``op`` at element width
    ``n`` bits, as the SIMDRAM operation set defines them."""
    if op == "if_else":
        return (1, n, n), (n,)
    if op in REDUCTIONS:
        return (n,) * 4, (n,)
    if op == "bitcount":
        return (n,), (n.bit_length(),)
    if op in ("relu", "abs"):
        return (n,), (n,)
    if op == "multiplication":
        return (n, n), (2 * n,)
    if op == "division":
        return (n, n), (n, n)
    if op in ("equal", "greater", "greater_equal"):
        return (n, n), (1,)
    if op in ("addition", "subtraction", "max", "min"):
        return (n, n), (n,)
    raise ValueError(f"unknown operation {op!r}")


def _signed(x: np.ndarray, n: int) -> np.ndarray:
    x = x.astype(np.int64) & mask(n)
    return np.where(x >= (1 << (n - 1)), x - (1 << n), x)


def evaluate(op: str, n: int, *xs: np.ndarray) -> Tuple[np.ndarray, ...]:
    """One operation over unsigned lanes: each operand is first cut to
    its declared width, each output is returned masked to its width as
    int64.  Division by zero gives an all-ones quotient and the dividend
    as remainder; ``relu`` and ``abs`` read their lanes as two's
    complement; the comparisons, ``max`` and ``min`` are unsigned."""
    in_w, out_w = widths(op, n)
    if len(xs) != len(in_w):
        raise ValueError(f"{op} takes {len(in_w)} operands, got {len(xs)}")
    v = [np.asarray(x).astype(np.int64) & mask(w) for x, w in zip(xs, in_w)]
    if op == "addition":
        out = (v[0] + v[1],)
    elif op == "subtraction":
        out = (v[0] - v[1],)
    elif op == "multiplication":
        out = ((v[0].astype(np.uint64) * v[1].astype(np.uint64))
               .astype(np.int64),)
    elif op == "division":
        zero = v[1] == 0
        safe = np.where(zero, 1, v[1])
        out = (np.where(zero, mask(n), v[0] // safe),
               np.where(zero, v[0], v[0] % safe))
    elif op == "equal":
        out = (v[0] == v[1],)
    elif op == "greater":
        out = (v[0] > v[1],)
    elif op == "greater_equal":
        out = (v[0] >= v[1],)
    elif op == "max":
        out = (np.maximum(v[0], v[1]),)
    elif op == "min":
        out = (np.minimum(v[0], v[1]),)
    elif op == "if_else":
        out = (np.where(v[0] == 1, v[1], v[2]),)
    elif op in REDUCTIONS:
        acc = v[0]
        for x in v[1:]:
            acc = REDUCTIONS[op](acc, x)
        out = (acc,)
    elif op == "bitcount":
        cnt = np.zeros_like(v[0])
        for i in range(n):
            cnt += (v[0] >> i) & 1
        out = (cnt,)
    elif op == "relu":
        s = _signed(v[0], n)
        out = (np.where(s < 0, 0, s),)
    else:                                   # abs
        out = (np.abs(_signed(v[0], n)),)
    return tuple(np.asarray(o).astype(np.int64) & mask(w)
                 for o, w in zip(out, out_w))


def tpch_q6(shipdate: np.ndarray, discount: np.ndarray,
            quantity: np.ndarray, price: np.ndarray,
            params: Dict[str, int]) -> Tuple[np.ndarray, np.ndarray, int]:
    """TPC-H Q6 over integer-coded columns:

      SELECT SUM(l_extendedprice * l_discount) FROM lineitem
      WHERE l_shipdate >= :date AND l_shipdate < :date + 1 year
        AND l_discount BETWEEN :discount - 0.01 AND :discount + 0.01
        AND l_quantity < :quantity

    ``params`` holds the bounds already coded like the columns
    (``date_lo``/``date_hi`` in days, ``disc_lo``/``disc_hi`` in
    hundredths, ``qty_lt``).  Returns the selection bit-vector, the
    per-row masked revenue and its SUM."""
    sel = ((shipdate >= params["date_lo"]) & (shipdate < params["date_hi"])
           & (discount >= params["disc_lo"]) & (discount <= params["disc_hi"])
           & (quantity < params["qty_lt"]))
    revenue = np.where(sel, price.astype(np.int64) * discount, 0)
    return sel, revenue, int(revenue.sum())


def range_scan(column: np.ndarray, lo: int, hi: int) -> np.ndarray:
    """BitWeaving/V range predicate ``lo <= column < hi`` as a
    selection bit-vector."""
    return (column >= lo) & (column < hi)


def lanes_wrong(got, want: np.ndarray) -> int:
    """Lanes of ``got`` that differ from ``want``; a missing or
    misshapen answer counts every lane as wrong."""
    want = np.asarray(want)
    if got is None:
        return int(want.size)
    got = np.asarray(got)
    if got.shape != want.shape:
        return int(want.size)
    return int(np.count_nonzero(got.astype(np.int64)
                                != want.astype(np.int64)))


def outputs_wrong(got: Sequence, want: Sequence[np.ndarray]) -> int:
    """Summed :func:`lanes_wrong` over an operation's outputs."""
    got = list(got) if got is not None else []
    got += [None] * (len(want) - len(got))
    return sum(lanes_wrong(g, w) for g, w in zip(got, want))
