"""Pallas kernel: the transposition unit (horizontal ↔ vertical layout).

SIMDRAM's memory-controller transposition unit converts 32 horizontal
words into 32 vertical bit-planes with a fixed wiring network.  The TPU
analogue is the classic SWAR 32×32 bit-matrix transpose: log₂32 = 5
rounds of masked shift/XOR swaps, fully vectorized across lane-words, so
each VPU op processes many independent 32×32 bit tiles at once.

Layout contract (matches repro.core.bitplane.pack):
  input  values  (N,)  uint32   — lane l's value
  output planes  (32, N/32) uint32 — plane j, word b holds bit j of lanes
                                      32b..32b+31 (lane l at bit l%32)

The word-level shuffle ``(N/32, 32) -> (32, N/32)`` happens outside the
kernel, so the kernel sees 32 rows — row l holds, for every word b, the
value of lane 32b+l — and the 32×32 bit transpose of each word column is
a fixed network over those rows: every partner exchange is a static row
pair, with no gather, reversal or in-kernel relayout.  The same network
maps planes back to values (a transpose is an involution), so h2v and
v2h share one kernel.

Tiling: a row of words is laid out (W/128, 128) so each row is dense
8×128 vregs; the grid steps over blocks of ``block_b`` words, each
instance holding a (32, block_b/128, 128) uint32 tile in VMEM (default
1,024 words: 128 KiB in, the same out).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import resolve_interpret

DEFAULT_BLOCK_B = 1024
_LANES = 128        # words per vreg row
_SUBLANES = 8       # vreg rows a multi-block tile must be a multiple of

# python ints (not traced constants): materialized inside the kernel body
_MASKS = (0x0000FFFF, 0x00FF00FF, 0x0F0F0F0F, 0x33333333, 0x55555555)
_DELTAS = (16, 8, 4, 2, 1)


def _swar_transpose_rows(x: list) -> list:
    """Main-diagonal 32×32 bit transpose over 32 row arrays.

    ``x[l]`` bit c is matrix element (l, c); returns ``y`` with ``y[c]``
    bit l = ``x[l]`` bit c.  Round ``j`` swaps the high ``j``-bit half
    of row k with the low half of row k+j in every 2j-row block
    (Hacker's Delight §7-3, with bit 0 as column 0)."""
    x = list(x)
    for j, m_int in zip(_DELTAS, _MASKS):
        m = jnp.uint32(m_int)
        sh = jnp.uint32(j)
        for k in range(32):
            if k & j:
                continue
            t = ((x[k] >> sh) ^ x[k + j]) & m
            x[k] = x[k] ^ (t << sh)
            x[k + j] = x[k + j] ^ t
    return x


def _kernel(in_ref, out_ref):
    rows = _swar_transpose_rows([in_ref[r] for r in range(32)])
    for r in range(32):
        out_ref[r] = rows[r]


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def _transpose_words(x: jax.Array, block_b: int,
                     interpret: Optional[bool]) -> jax.Array:
    """(32, W) uint32 -> (32, W): the bit transpose of every word column."""
    w = x.shape[1]
    n_rows = -(-w // _LANES)
    blk = max(1, block_b // _LANES)
    if blk >= n_rows:
        blk = n_rows                    # one block spans the whole array
    else:
        blk = -(-blk // _SUBLANES) * _SUBLANES
    n_rows = -(-n_rows // blk) * blk    # partial tail tile pads with zeros
    x = jnp.pad(x, ((0, 0), (0, n_rows * _LANES - w)))
    x = x.reshape(32, n_rows, _LANES)
    spec = pl.BlockSpec((32, blk, _LANES), lambda i: (0, i, 0))
    out = pl.pallas_call(
        _kernel,
        grid=(n_rows // blk,),
        in_specs=[spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(x.shape, jnp.uint32),
        interpret=resolve_interpret(interpret),
    )(x)
    return out.reshape(32, n_rows * _LANES)[:, :w]


def h2v_pallas(values: jax.Array, *, block_b: int = DEFAULT_BLOCK_B,
               interpret: Optional[bool] = None) -> jax.Array:
    """(N,) uint32 -> (32, N/32) uint32 planes.

    N must be a multiple of 32; any word count is accepted — a partial
    tail tile is zero-padded up to the block so the grid always divides
    evenly, and the pad is sliced off the result.
    """
    n = values.shape[0]
    assert n % 32 == 0
    nb = n // 32
    if nb == 0:
        return jnp.zeros((32, 0), jnp.uint32)
    x = values.astype(jnp.uint32).reshape(nb, 32).T
    return _transpose_words(x, block_b, interpret)


def v2h_pallas(planes: jax.Array, *, block_b: int = DEFAULT_BLOCK_B,
               interpret: Optional[bool] = None) -> jax.Array:
    """(32, N/32) uint32 planes -> (N,) uint32 lane values.

    Accepts any word count (partial tail tiles zero-pad to the block and
    the pad is sliced off the result)."""
    nb = planes.shape[1]
    if nb == 0:
        return jnp.zeros((0,), jnp.uint32)
    x = _transpose_words(planes.astype(jnp.uint32), block_b, interpret)
    return x.T.reshape(nb * 32)
