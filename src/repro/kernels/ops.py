"""jit'd public wrappers for the Pallas kernels (+ padding & dispatch).

  bbop_pallas            — any of the 16 SIMDRAM ops, fused-circuit kernel
  h2v / v2h              — transposition unit (SWAR kernel)
  bitserial_matmul       — multi-bit integer matmul over binary popcount
                           matmuls (sign-aware, two's complement)
  quantized_matmul       — offload-style dispatch: bit-serial path for
                           ≤2-bit operands, jnp (MXU) int path otherwise

``interpret=None`` (the default) compiles the kernels on an accelerator
and interprets them on the CPU; pass a bool to force either.  Oracles in
ref.py.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.bitplane import _compiled_op, pack, unpack
from . import ref
from .bitplane_ops import circuit_on_planes
from .bitserial_matmul import binary_matmul
from .transpose_kernel import h2v_pallas, v2h_pallas


def _pad_axis(x: jax.Array, axis: int, mult: int) -> Tuple[jax.Array, int]:
    n = x.shape[axis]
    target = -(-n // mult) * mult
    if target == n:
        return x, n
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, target - n)
    return jnp.pad(x, pad), n


def bbop_pallas(
    name: str,
    n_bits: int,
    *operands: jax.Array,
    signed_out: bool = False,
    block_w: int = 512,
    interpret: Optional[bool] = None,
):
    """Execute one SIMDRAM op via the fused bit-plane Pallas kernel."""
    spec, circ, ids = _compiled_op(name, n_bits)
    n = operands[0].shape[-1]
    lane_mult = 32 * block_w
    padded = [
        _pad_axis(jnp.asarray(o).reshape(-1), 0, lane_mult)[0] for o in operands
    ]
    planes = [pack(o, w) for o, w in zip(padded, spec.operand_bits)]
    out_planes = circuit_on_planes(
        circ, ids, planes, block_w=block_w, interpret=interpret
    )
    outs = []
    pos = 0
    for w in spec.out_bits:
        vals = unpack(out_planes[pos: pos + w], signed=signed_out)[:n]
        outs.append(vals)
        pos += w
    return outs[0] if len(outs) == 1 else tuple(outs)


def h2v(values: jax.Array, n_bits: int = 32, *,
        interpret: Optional[bool] = None) -> jax.Array:
    """Transposition unit, horizontal→vertical; returns (n_bits, N/32).

    Any lane count N is accepted (lanes pad to a multiple of 32, the
    kernel pads partial tiles internally).  This is the conversion the
    bank dispatcher's ``VerticalOperand.from_values`` routes through —
    and the one its operand forwarding *skips* for chained bbops.
    """
    assert n_bits <= 32, "h2v packs machine words; use core.subarray for wider"
    v, n = _pad_axis(values.astype(jnp.uint32).reshape(-1), 0, 32)
    planes = h2v_pallas(v, interpret=interpret)
    return planes[:n_bits]


def v2h(planes: jax.Array, *, signed: bool = False,
        interpret: Optional[bool] = None) -> jax.Array:
    """Transposition unit, vertical→horizontal; accepts (k≤32, W) planes
    for any word count W (the kernel pads partial tiles internally)."""
    k, w = planes.shape
    if k < 32:
        planes = jnp.concatenate(
            [planes, jnp.zeros((32 - k, w), jnp.uint32)], axis=0
        )
    vals = v2h_pallas(planes, interpret=interpret)
    if signed and k < 32:
        sign = (vals >> jnp.uint32(k - 1)) & jnp.uint32(1)
        return vals.astype(jnp.int32) - (sign.astype(jnp.int32) << k)
    return vals.astype(jnp.int32)


def _pack_bits_matrix(x: jax.Array, axis_k: int) -> jax.Array:
    """Pack a {0,1} int matrix along axis `axis_k` into uint32 words."""
    x = x.astype(jnp.uint32)
    x = jnp.moveaxis(x, axis_k, -1)
    kw = x.shape[-1] // 32
    x = x.reshape(*x.shape[:-1], kw, 32)
    shifts = jnp.arange(32, dtype=jnp.uint32)
    words = (x << shifts).sum(axis=-1, dtype=jnp.uint32)
    return jnp.moveaxis(words, -1, axis_k)


def bitserial_matmul(
    a: jax.Array,
    w: jax.Array,
    a_bits: int,
    w_bits: int,
    *,
    a_signed: bool = False,
    w_signed: bool = True,
    bm: int = 128,
    bn: int = 128,
    bk: int = 64,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Integer matmul  (M,K) × (K,N) -> (M,N) int32, computed bit-serially.

    Decomposes into a_bits × w_bits binary popcount-matmuls on the Pallas
    kernel; MSB planes of signed operands carry negative weight.
    """
    m, k = a.shape
    k2, n = w.shape
    assert k == k2
    # a 1-bit two's-complement type would be {0,-1}: 1-bit operands are
    # always unsigned {0,1}
    a_signed = a_signed and a_bits > 1
    w_signed = w_signed and w_bits > 1
    au = a.astype(jnp.int32) & ((1 << a_bits) - 1)
    wu = w.astype(jnp.int32) & ((1 << w_bits) - 1)
    # pad K to 32·bk words, M/N to tile multiples
    kw_mult = 32 * bk
    au, _ = _pad_axis(au, 1, kw_mult)
    wu, _ = _pad_axis(wu, 0, kw_mult)
    au, m0 = _pad_axis(au, 0, bm)
    wu, n0 = _pad_axis(wu, 1, bn)

    out = jnp.zeros((au.shape[0], wu.shape[1]), jnp.int32)
    for i in range(a_bits):
        sa = -1 if (a_signed and i == a_bits - 1) else 1
        a_planes = _pack_bits_matrix((au >> i) & 1, axis_k=1)   # (M, Kw)
        for j in range(w_bits):
            sw = -1 if (w_signed and j == w_bits - 1) else 1
            w_planes = _pack_bits_matrix((wu >> j) & 1, axis_k=0)  # (Kw, N)
            part = binary_matmul(
                a_planes, w_planes, bm=bm, bn=bn, bk=bk, interpret=interpret
            )
            out = out + (sa * sw) * (part << (i + j))
    return out[:m0, :n0]


def quantized_matmul(
    a: jax.Array, w: jax.Array, a_bits: int, w_bits: int, **kw
) -> jax.Array:
    """Offload-style dispatch (the paper's §4 decision, TPU edition):
    bit-serial pays off only for very low precision; otherwise the MXU
    int path wins (see DESIGN.md hardware-adaptation notes)."""
    if a_bits * w_bits <= 4:
        return bitserial_matmul(a, w, a_bits, w_bits, **kw)
    return jnp.dot(
        a.astype(jnp.int32), w.astype(jnp.int32), preferred_element_type=jnp.int32
    )
