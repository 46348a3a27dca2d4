"""Pallas kernels vs ref.py oracles: shape/dtype sweeps.

On the CPU the kernels run in interpret mode; tests/test_tpu_compile.py
compiles the same kernels for a TPU."""

import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.kernels import ops as kops
from repro.kernels import ref
from repro.kernels.bitserial_matmul import binary_matmul
from repro.core import bitplane
from repro.kernels.transpose_kernel import (DEFAULT_BLOCK_B, h2v_pallas,
                                            v2h_pallas)


# -- transpose kernel ---------------------------------------------------------

@pytest.mark.parametrize("n", [32, 64, 256, 1024])
def test_h2v_matches_ref(n):
    rng = np.random.default_rng(n)
    v = jnp.asarray(rng.integers(0, 2**32, size=n, dtype=np.uint32))
    got = h2v_pallas(v, block_b=min(8, n // 32))
    want = ref.transpose32_ref(v)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@given(st.integers(0, 2**31 - 1))
@settings(max_examples=20, deadline=None)
def test_transpose_involution(seed):
    rng = np.random.default_rng(seed)
    v = jnp.asarray(rng.integers(0, 2**32, size=128, dtype=np.uint32))
    planes = h2v_pallas(v, block_b=4)
    back = v2h_pallas(planes, block_b=4)
    np.testing.assert_array_equal(np.asarray(back), np.asarray(v))


@pytest.mark.parametrize("lanes", [
    32 * (DEFAULT_BLOCK_B + 130),   # one full block + a partial tail tile
    65_536,
])
def test_transpose_bit_exact_at_width(lanes):
    rng = np.random.default_rng(lanes)
    v = jnp.asarray(rng.integers(0, 2**32, size=lanes, dtype=np.uint32))
    planes = h2v_pallas(v)
    np.testing.assert_array_equal(np.asarray(planes),
                                  np.asarray(ref.transpose32_ref(v)))
    np.testing.assert_array_equal(np.asarray(planes),
                                  np.asarray(bitplane.pack(v, 32)))
    np.testing.assert_array_equal(np.asarray(v2h_pallas(planes)),
                                  np.asarray(v))


# -- binary popcount matmul ---------------------------------------------------

@pytest.mark.parametrize("m,kw,n,bm,bn,bk", [
    (8, 2, 8, 8, 8, 2),
    (16, 4, 32, 8, 16, 2),
    (32, 8, 16, 16, 16, 4),
])
def test_binary_matmul_sweep(m, kw, n, bm, bn, bk):
    rng = np.random.default_rng(m * n)
    a = jnp.asarray(rng.integers(0, 2**32, size=(m, kw), dtype=np.uint32))
    w = jnp.asarray(rng.integers(0, 2**32, size=(kw, n), dtype=np.uint32))
    got = binary_matmul(a, w, bm=bm, bn=bn, bk=bk)
    want = ref.binary_matmul_ref(a, w)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("a_bits,w_bits,a_signed,w_signed", [
    (1, 1, False, False),
    (2, 2, False, True),
    (4, 4, False, True),
    (3, 5, True, True),
])
def test_bitserial_matmul_vs_int(a_bits, w_bits, a_signed, w_signed):
    rng = np.random.default_rng(a_bits * 10 + w_bits)
    m, k, n = 8, 64, 12
    alo = -(1 << (a_bits - 1)) if a_signed else 0
    ahi = (1 << (a_bits - 1)) if a_signed else (1 << a_bits)
    wlo = -(1 << (w_bits - 1)) if w_signed else 0
    whi = (1 << (w_bits - 1)) if w_signed else (1 << w_bits)
    a = rng.integers(alo, ahi, size=(m, k)).astype(np.int32)
    w = rng.integers(wlo, whi, size=(k, n)).astype(np.int32)
    got = kops.bitserial_matmul(jnp.asarray(a), jnp.asarray(w),
                                a_bits, w_bits, a_signed=a_signed,
                                w_signed=w_signed, bm=8, bn=4, bk=2)
    np.testing.assert_array_equal(np.asarray(got), a @ w)
    # and the jnp reference agrees too
    r = ref.bitserial_matmul_ref(jnp.asarray(a), jnp.asarray(w),
                                 a_bits, w_bits, a_signed, w_signed)
    np.testing.assert_array_equal(np.asarray(r), a @ w)


def test_quantized_matmul_dispatch():
    rng = np.random.default_rng(0)
    a = rng.integers(0, 2, size=(8, 64)).astype(np.int32)
    w = rng.integers(0, 2, size=(64, 8)).astype(np.int32)
    got = kops.quantized_matmul(jnp.asarray(a), jnp.asarray(w), 1, 1)
    np.testing.assert_array_equal(np.asarray(got), a @ w)
    a8 = rng.integers(-128, 128, size=(4, 16)).astype(np.int32)
    w8 = rng.integers(-128, 128, size=(16, 4)).astype(np.int32)
    got = kops.quantized_matmul(jnp.asarray(a8), jnp.asarray(w8), 8, 8)
    np.testing.assert_array_equal(np.asarray(got), a8 @ w8)


# -- fused elementwise circuit kernel ----------------------------------------

@pytest.mark.parametrize("name,n_bits", [
    ("addition", 8), ("subtraction", 8), ("greater", 8),
    ("relu", 8), ("if_else", 6), ("equal", 12),
])
def test_bbop_pallas_sweep(name, n_bits):
    from repro.core.ops_library import get_op
    spec = get_op(name, n_bits)
    rng = np.random.default_rng(7)
    ops_vals = [rng.integers(0, 1 << w, size=200).astype(np.int32)
                for w in spec.operand_bits]
    got = kops.bbop_pallas(name, n_bits, *[jnp.asarray(v) for v in ops_vals],
                           block_w=8)
    got = got if isinstance(got, tuple) else (got,)
    want = spec.oracle(*[v.astype(np.uint64) for v in ops_vals])
    for gi, (g, e) in enumerate(zip(got, want)):
        mask = (1 << spec.out_bits[gi]) - 1
        np.testing.assert_array_equal(np.asarray(g).astype(np.int64) & mask,
                                      e.astype(np.int64) & mask)
