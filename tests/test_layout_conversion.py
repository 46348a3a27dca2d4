"""Layout conversion both ways: ``subarray.pack_bits`` and its caller
``control_unit.load_state``, ``subarray.unpack_bits`` and its caller
``control_unit.read_outputs``, against plain per-lane, per-bit
references written here."""

from types import SimpleNamespace

import numpy as np
import pytest

from repro.core.bank import cached_table
from repro.core.control_unit import load_state, read_outputs
from repro.core.subarray import pack_bits, unpack_bits
from repro.core.uprogram import C0, C1

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


def _pack_reference(values, n_bits, n_columns=N_WORDS * 32):
    """Bit ``p`` of lane ``c``'s two's complement at bit ``c % 32`` of
    word ``c // 32`` of plane ``p``; nothing past the values."""
    planes = np.zeros((n_bits, n_columns // 32), dtype=np.uint32)
    for lane, v in enumerate(values):
        v = int(v)                    # Python ints sign-extend forever
        w, b = divmod(lane, 32)
        for p in range(n_bits):
            if (v >> p) & 1:
                planes[p, w] |= np.uint32(1 << b)
    return planes


@pytest.mark.parametrize("lanes", [0, 1, 7, 8, 33, 1000, N_WORDS * 32])
@pytest.mark.parametrize("n_bits", [0, 1, 7, 8, 9, 16, 24, 31, 32, 33, 48,
                                    63, 64])
def test_pack_bits_matches_per_bit_reference(n_bits, lanes):
    # all 64 bits of every value are random: those at and above
    # ``n_bits`` must not reach the planes
    rng = np.random.default_rng(1000 * n_bits + lanes)
    values = rng.integers(0, np.iinfo(np.uint64).max, lanes,
                          dtype=np.uint64, endpoint=True)
    got = pack_bits(values, n_bits, N_WORDS * 32)
    assert got.dtype == np.uint32 and got.shape == (n_bits, N_WORDS)
    np.testing.assert_array_equal(got, _pack_reference(values, n_bits))
    # every column past ``lanes`` is zero
    cols = np.unpackbits(got.view(np.uint8), axis=1, bitorder="little")
    assert not cols[:, lanes:].any()


def _typed_values(dtype, lanes, rng):
    if dtype is bool:
        return rng.integers(0, 2, lanes).astype(bool)
    info = np.iinfo(dtype)
    v = rng.integers(info.min, info.max, lanes, dtype=dtype, endpoint=True)
    v[:2] = info.min, info.max        # the extremes, negative for signed
    return v


@pytest.mark.parametrize("n_bits", [1, 7, 8, 9, 16, 33, 64])
@pytest.mark.parametrize("dtype", [np.int64, np.uint64, np.int32, np.uint8,
                                   bool])
def test_pack_bits_takes_any_integer_dtype_as_twos_complement(dtype, n_bits):
    # narrower dtypes than n_bits sign-extend; no caller widens first
    values = _typed_values(dtype, 200, np.random.default_rng(n_bits))
    np.testing.assert_array_equal(pack_bits(values, n_bits, N_WORDS * 32),
                                  _pack_reference(values, n_bits))


@pytest.mark.parametrize("n_bits", [1, 8, 24, 48, 64])
def test_pack_then_unpack_round_trips(n_bits):
    lanes = 777
    rng = np.random.default_rng(n_bits)
    values = rng.integers(0, np.iinfo(np.uint64).max, lanes,
                          dtype=np.uint64, endpoint=True)
    got = unpack_bits(pack_bits(values, n_bits, N_WORDS * 32), lanes)
    mask = np.uint64((1 << n_bits) - 1)
    np.testing.assert_array_equal(got, values & mask)


def test_load_state_packs_a_negative_operand_as_is():
    _, uprog, _ = cached_table("addition", 16)
    rng = np.random.default_rng(3)
    a = rng.integers(-(1 << 40), 1 << 40, 300, dtype=np.int64)
    a[:2] = np.iinfo(np.int64).min, -1
    b = rng.integers(0, 1 << 16, 300).astype(np.uint16)
    state = load_state(uprog, [a, b], N_WORDS * 32)
    for operand, rows in zip((a, b), uprog.in_rows):
        np.testing.assert_array_equal(
            state[list(rows)], _pack_reference(operand, len(rows)))
    assert (state[C1] == np.uint32(0xFFFFFFFF)).all()
    assert not state[C0].any()
