"""Vertical -> horizontal conversion: ``subarray.unpack_bits`` and its
caller ``control_unit.read_outputs`` against plain per-lane, per-bit
references written here."""

from types import SimpleNamespace

import numpy as np
import pytest

from repro.core.control_unit import read_outputs
from repro.core.subarray import unpack_bits

N_WORDS = 32                      # 1,024 columns a plane


def _bit(planes, p, lane):
    return (int(planes[p, lane // 32]) >> (lane % 32)) & 1


def _reference(planes, lanes):
    return np.array([sum(_bit(planes, p, lane) << p
                         for p in range(planes.shape[0]))
                     for lane in range(lanes)], dtype=np.uint64)


@pytest.mark.parametrize("lanes", [0, 1, 7, 8, 33, 1000, N_WORDS * 32])
@pytest.mark.parametrize("n_bits", [0, 1, 7, 8, 9, 16, 24, 31, 32, 33, 48,
                                    63, 64])
def test_unpack_bits_matches_per_bit_reference(n_bits, lanes):
    # every plane bit is random, those past ``lanes`` included: garbage
    # there must not reach the result
    rng = np.random.default_rng(1000 * n_bits + lanes)
    planes = rng.integers(0, 1 << 32, (n_bits, N_WORDS), dtype=np.uint32)
    got = unpack_bits(planes, lanes)
    assert got.dtype == np.uint64 and got.shape == (lanes,)
    np.testing.assert_array_equal(got, _reference(planes, lanes))


def _plant(row, values, lanes, p):
    """Write bit ``p`` of each of ``lanes`` values into plane ``row``,
    leaving the bits past ``lanes`` as they were."""
    for lane in range(lanes):
        w, b = divmod(lane, 32)
        word = int(row[w]) & ~(1 << b) | (((int(values[lane]) >> p) & 1) << b)
        row[w] = word


@pytest.mark.parametrize("signed", [False, True])
def test_read_outputs_narrows_each_output_to_int64(signed):
    widths = [24, 48, 5]
    lanes = 200                                 # 7 words, the last partial
    rng = np.random.default_rng(7)
    state = rng.integers(0, 1 << 32, (2 + sum(widths), 7), dtype=np.uint32)
    values, rows, row = [], [], 2
    for w in widths:
        v = [int(x) for x in rng.integers(-(1 << (w - 1)), 1 << (w - 1),
                                          lanes)]
        v[:2] = [-(1 << (w - 1)), -1]           # the extremes, negative
        values.append(v)
        for p in range(w):
            _plant(state[row], [x & ((1 << w) - 1) for x in v], lanes, p)
            rows.append((row, False))
            row += 1
    uprog = SimpleNamespace(out_rows=rows)
    before = state.copy()
    outs = read_outputs(widths, uprog, state, lanes, signed=signed)
    assert len(outs) == len(widths)
    for w, v, got in zip(widths, values, outs):
        want = v if signed else [x & ((1 << w) - 1) for x in v]
        assert got.dtype == np.int64 and got.shape == (lanes,)
        np.testing.assert_array_equal(got, np.array(want, dtype=np.int64))
        assert not np.shares_memory(got, state)
    assert not any(np.shares_memory(a, b)
                   for i, a in enumerate(outs) for b in outs[i + 1:])
    np.testing.assert_array_equal(state, before)
