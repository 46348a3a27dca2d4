"""Faithful row-granular DRAM subarray simulator (SIMDRAM Step 3 substrate).

The subarray is a ``(n_rows, n_words)`` uint32 array: row *r*, bit-column
*c* is bit ``c % 32`` of word ``c // 32`` — i.e. each row is a 1-bit-tall
bit-vector across all DRAM columns (SIMD lanes).  Vertical data layout means
operand bit *j* of every lane lives in one row.

Semantics implemented exactly as the hardware primitives:

  - ``AAP(src, dst)``: dst row := value read through ``src`` port.  Writing
    a DCC row through its n-port stores the complement at the d-port (the
    array always stores the d-port value).
  - ``AP(triple)``: the three rows (read through their port polarities)
    charge-share; **all three** rows end up holding MAJ of the three read
    values (n-port participants store the complement physically).

C0/C1 are pinned constant rows.  This simulator is the correctness oracle
for Step 2's μPrograms: `tests/test_uprogram.py` proves every compiled op
equals its integer oracle for both the SIMDRAM (MIG) and Ambit (AIG)
programs.

The fast TPU path (bit-plane backend + Pallas kernels) is in
:mod:`repro.core.bitplane` / :mod:`repro.kernels`; the scan/switch-based
programmable control unit is in :mod:`repro.core.control_unit`.
"""

from __future__ import annotations

from typing import List, Sequence, Tuple

import numpy as np

from .uprogram import C0, C1, DCC_ROWS, TRIPLES, Command, RowRef, UProgram


class Subarray:
    """Numpy-backed row-granular simulator (exact, used as oracle)."""

    def __init__(self, n_rows: int, n_columns: int):
        assert n_columns % 32 == 0
        self.n_rows = n_rows
        self.n_words = n_columns // 32
        self.n_columns = n_columns
        self.rows = np.zeros((n_rows, self.n_words), dtype=np.uint32)
        self.rows[C1] = np.uint32(0xFFFFFFFF)
        self.activation_count = np.zeros(n_rows, dtype=np.int64)

    # --- port-level access -----------------------------------------------
    def read(self, ref: RowRef) -> np.ndarray:
        row, neg = ref
        v = self.rows[row]
        return ~v if neg else v

    def write(self, ref: RowRef, value: np.ndarray) -> None:
        row, neg = ref
        if row in (C0, C1):
            raise ValueError("constant rows are read-only")
        self.rows[row] = (~value if neg else value).astype(np.uint32)

    # --- DRAM commands ------------------------------------------------------
    def aap(self, src: RowRef, dst: RowRef) -> None:
        self.activation_count[src[0]] += 1
        self.activation_count[dst[0]] += 1
        self.write(dst, self.read(src))

    def ap(self, triple_idx: int) -> None:
        triple = TRIPLES[triple_idx]
        vals = [self.read(ref) for ref in triple]
        maj = (vals[0] & vals[1]) | (vals[0] & vals[2]) | (vals[1] & vals[2])
        for ref in triple:
            self.activation_count[ref[0]] += 1
            self.write(ref, maj)

    def execute(self, cmds: Sequence[Command]) -> None:
        for c in cmds:
            if c.kind == "AAP":
                self.aap(c.src, c.dst)
            else:
                self.ap(c.triple)


# ---------------------------------------------------------------------------
# vertical-layout helpers (transposition-unit functionality, numpy side)
# ---------------------------------------------------------------------------

# Delta swaps that transpose an 8x8 bit matrix held in a uint64 (bit
# 8r + c <-> bit 8c + r): swap the 2x2 blocks' off-diagonal bits, then
# those of the 4x4 blocks, then of the two 4x4 halves.
_TRANSPOSE8 = tuple((np.uint64(shift), np.uint64(mask)) for shift, mask in
                    ((7, 0x00AA00AA00AA00AA), (14, 0x0000CCCC0000CCCC),
                     (28, 0x00000000F0F0F0F0)))


def pack_bits(values: np.ndarray, n_bits: int, n_columns: int) -> np.ndarray:
    """Horizontal -> vertical: (lanes,) integers -> (n_bits, n_words) uint32.

    Plane *p* holds bit *p* of each value's two's complement, lane *c* at
    bit ``c % 32`` of word ``c // 32``; columns past ``lanes`` are zero.
    A bit-matrix transpose on the values' little-endian bytes, the
    inverse of :func:`unpack_bits`: byte *g* of 8 consecutive lanes,
    gathered into one uint64 and transposed as an 8×8 bit matrix, is
    byte ``lane // 8`` of planes 8g…8g+7.  Only the ``ceil(n_bits / 8)``
    byte columns that hold a plane are read, and the values are widened
    (sign-extending) only where their dtype is narrower than that.
    Temporaries hold about one byte per lane per byte column."""
    lanes = values.shape[0]
    assert lanes <= n_columns
    nb = -(-n_bits // 8)                # byte columns that hold a plane
    groups = -(-lanes // 8)             # plane bytes that hold a lane
    if values.dtype.itemsize < nb:
        values = values.astype(np.uint64)
    by = np.ascontiguousarray(values).view(np.uint8).reshape(
        lanes, values.dtype.itemsize)
    cols = np.zeros((nb, groups * 8), dtype=np.uint8)
    cols[:, :lanes] = by[:, :nb].T
    x = cols.view(np.uint64)            # (nb, groups): 8 lanes' byte g
    t = np.empty_like(x)
    for shift, mask in _TRANSPOSE8:
        np.right_shift(x, shift, out=t)
        t ^= x
        t &= mask
        x ^= t
        t <<= shift
        x ^= t
    out = np.zeros((nb, 8, n_columns // 8), dtype=np.uint8)
    out[:, :, :groups] = cols.reshape(nb, groups, 8).transpose(0, 2, 1)
    return out.reshape(nb * 8, n_columns // 8)[:n_bits].view(np.uint32)


# _SPREAD[i, b]: byte b's bit k moved to bit i of byte k.  One plane
# byte holds one bit of 8 lanes; spreading it puts that bit into each
# lane's byte, at the plane's place within its group of 8 planes.
_SPREAD = (np.unpackbits(np.arange(256, dtype=np.uint8)[:, None], axis=1,
                         bitorder="little").view(np.uint64).reshape(1, 256)
           << np.arange(8, dtype=np.uint64)[:, None])
_SPREAD_ROW = np.arange(0, _SPREAD.size, 256, dtype=np.intp)[:, None]


def unpack_bits(planes: np.ndarray, lanes: int) -> np.ndarray:
    """Vertical -> horizontal: (n_bits, n_words) uint32 -> (lanes,) uint64.

    A bit-matrix transpose on the packed bytes: byte *g* of plane *p*
    holds bit *p* of lanes 8g…8g+7.  Each group of 8 planes is looked up
    in ``_SPREAD`` and OR-ed together — the 8×8 transpose — which gives,
    viewed as bytes, output byte ``p // 8`` of each of the 8 lanes; it
    lands in that byte column of a (lanes, 8) uint8 buffer, viewed as
    uint64 at the end.  Temporaries hold about one byte per lane per
    plane; plane bits past ``lanes`` are ignored."""
    groups = -(-lanes // 8)          # plane bytes that hold a lane
    by = np.ascontiguousarray(planes).view(np.uint8)[:, :groups]
    out = np.zeros((groups * 8, 8), dtype=np.uint8)
    for q in range(0, planes.shape[0], 8):
        idx = by[q:q + 8].astype(np.intp)
        idx += _SPREAD_ROW[:len(idx)]
        out[:, q // 8] = np.bitwise_or.reduce(
            np.take(_SPREAD, idx), axis=0).view(np.uint8)
    return out.view(np.uint64).reshape(-1)[:lanes]


def run_uprogram(
    uprog: UProgram, operands: Sequence[np.ndarray], n_columns: int = 256
) -> List[np.ndarray]:
    """Load operands vertically, execute the μProgram, read back outputs.

    ``operands[i]`` is a (lanes,) integer array for operand *i*.  Returns one
    (lanes,) uint64 array per output row group (1 bit per group; callers
    regroup via ``uprog.out_rows`` widths — see :func:`run_op`).
    """
    lanes = operands[0].shape[0]
    sa = Subarray(uprog.n_rows_total, n_columns)
    for op_idx, rows in enumerate(uprog.in_rows):
        planes = pack_bits(np.asarray(operands[op_idx]), len(rows), n_columns)
        for j, r in enumerate(rows):
            sa.rows[r] = planes[j]
    sa.execute(uprog.commands)
    outs = []
    for rows in uprog.out_rows:
        planes = np.stack([sa.rows[r] for r in rows])
        outs.append(unpack_bits(planes, lanes))
    return outs


def run_op(
    uprog: UProgram,
    out_widths: Sequence[int],
    operands: Sequence[np.ndarray],
    n_columns: int = 256,
) -> List[np.ndarray]:
    """Like :func:`run_uprogram` but regroups single-bit outputs into the
    op's declared output widths (e.g. 8 sum rows -> one 8-bit result)."""
    flat = run_uprogram(uprog, operands, n_columns)
    outs: List[np.ndarray] = []
    pos = 0
    for w in out_widths:
        acc = np.zeros_like(flat[0])
        for j in range(w):
            acc |= (flat[pos + j] & np.uint64(1)) << np.uint64(j)
        outs.append(acc)
        pos += w
    return outs
