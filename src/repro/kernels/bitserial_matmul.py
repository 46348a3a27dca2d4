"""Pallas kernel: bit-serial integer matmul (SIMDRAM's NN-kernel engine).

SIMDRAM computes quantized NN layers with bit-serial MACs over vertical
data.  The TPU-native formulation decomposes an integer matmul over
bit-planes:

    A·W = Σ_{i<a_bits} Σ_{j<w_bits} s_i·s_j·2^{i+j} · popcount-matmul(Aᵢ, Wⱼ)

where Aᵢ, Wⱼ are bit-packed binary matrices (32 features/uint32 word) and
popcount-matmul is  out[m,n] = Σ_k popcount(a[m,k] & w[k,n]) — the paper's
AND + bitcount inner loop, one full 32-feature block per VPU op.

This kernel implements popcount-matmul with VMEM tiling:

  grid (M/BM, N/BN, Kw/BK); A tile (BM, BK) uint32, W tile (BK, BN) uint32
  accumulator (BM, BN) int32 lives in the output block (revisited across
  the K grid axis — Pallas keeps it resident in VMEM between K steps).

VMEM budget per instance: BM·BK + BK·BN + BM·BN words.  Defaults
(BM=BN=128, BK=64) give 128·64 + 64·128 + 128·128 ≈ 32 K words = 128 KiB.
The inner product expands a (BM, 1, BK) & (1, BN, BK)... no — to stay
vector-friendly we loop over the BK words with a fori_loop, each step
doing a rank-1 popcount update on an (BM, BN) vreg-tiled block: AND of a
broadcast column/row pair + SWAR popcount + add.  Mosaic maps these to
plain VPU ops — no MXU involvement.

Honest hardware-adaptation note (recorded in DESIGN.md/EXPERIMENTS.md):
on real TPUs the MXU computes int8 matmuls natively, so the bit-serial
path only wins for ≤2-bit operands (binary/ternary nets) or when the MXU
is saturated; `ops.quantized_matmul` picks the path per cost model — the
same role SIMDRAM's offload decision plays against the CPU.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import resolve_interpret

def _popcount(v: jax.Array) -> jax.Array:
    # masks constructed inside the traced body (pallas kernels cannot
    # capture module-level device constants)
    m1, m2, m4 = jnp.uint32(0x55555555), jnp.uint32(0x33333333), jnp.uint32(0x0F0F0F0F)
    h01 = jnp.uint32(0x01010101)
    v = v - ((v >> 1) & m1)
    v = (v & m2) + ((v >> 2) & m2)
    v = (v + (v >> 4)) & m4
    return ((v * h01) >> 24).astype(jnp.int32)


def _kernel(a_ref, w_ref, out_ref):
    """One (BM, BN) tile, accumulating over the K grid axis."""
    k_step = pl.program_id(2)

    @pl.when(k_step == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    a = a_ref[...]            # (BM, BK) uint32
    w = w_ref[...]            # (BK, BN) uint32
    bk = a.shape[1]

    def body(k, acc):
        a_col = jax.lax.dynamic_slice_in_dim(a, k, 1, axis=1)   # (BM, 1)
        w_row = jax.lax.dynamic_slice_in_dim(w, k, 1, axis=0)   # (1, BN)
        return acc + _popcount(a_col & w_row)

    acc = jax.lax.fori_loop(0, bk, body, jnp.zeros(out_ref.shape, jnp.int32))
    out_ref[...] += acc


def binary_matmul(  # noqa: D401
    a_words: jax.Array,
    w_words: jax.Array,
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 64,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """out[m,n] = Σ_k popcount(a_words[m,k] & w_words[k,n]).

    a_words: (M, Kw) uint32, w_words: (Kw, N) uint32 -> (M, N) int32.
    Shapes must tile evenly (callers pad; see ops.bitserial_matmul).
    """
    m, kw = a_words.shape
    kw2, n = w_words.shape
    assert kw == kw2
    bm = min(bm, m)
    bn = min(bn, n)
    bk = min(bk, kw)
    assert m % bm == 0 and n % bn == 0 and kw % bk == 0, (m, n, kw, bm, bn, bk)

    fn = pl.pallas_call(
        _kernel,
        grid=(m // bm, n // bn, kw // bk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int32),
        interpret=resolve_interpret(interpret),
    )
    return fn(a_words.astype(jnp.uint32), w_words.astype(jnp.uint32))
