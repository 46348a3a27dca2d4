"""Pallas kernel: fused execution of a SIMDRAM circuit on bit-planes.

The TPU analogue of Step 3: instead of a μProgram replayed row-by-row in
DRAM, the whole MAJ/NOT circuit executes inside ONE kernel invocation per
lane-tile, with every intermediate living in VMEM (the analogue of compute
rows) and the straight-line MAJ/NOT program running on the VPU.

Tiling / VMEM budget
--------------------
Operand planes arrive as (total_in_bits, W) uint32; outputs are
(total_out_bits, W).  The grid tiles the lane-word axis W; each program
instance sees a (bits, BLOCK_W) tile.  VMEM per instance ≈
(in_bits + out_bits + live_intermediates) · BLOCK_W · 4 B.  With the
default BLOCK_W = 512 (= 4 lanes · 128-wide vregs, 2 KiB per plane) even a
64-deep multiplier circuit stays ≪ 1 MiB, far under the ~16 MiB VMEM of a
v5e core; BLOCK_W is exposed for the perf sweep in benchmarks.

The kernel body is generated per circuit (unrolled MAJ/NOT ops); Mosaic
sees only 8×128-lane uint32 bitwise ops — the precise TPU mapping of the
paper's "one TRA = one command" inner loop.
"""

from __future__ import annotations

import functools
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.logic import Circuit

from . import resolve_interpret

DEFAULT_BLOCK_W = 512


def _make_kernel(circ: Circuit, input_ids_flat: Tuple[Tuple[int, ...], ...]):
    """Build the kernel body executing `circ` on plane tiles."""

    def kernel(*refs):
        in_refs = refs[: len(input_ids_flat)]
        out_ref = refs[-1]
        w = in_refs[0].shape[-1]
        zero = jnp.zeros((w,), jnp.uint32)
        one = jnp.full((w,), jnp.uint32(0xFFFFFFFF))
        inputs = {}
        for ids, ref in zip(input_ids_flat, in_refs):
            block = ref[...]
            for j, nid in enumerate(ids):
                inputs[nid] = block[j]
        outs = circ.evaluate_outputs(inputs, zero, one)
        out_ref[...] = jnp.stack([o + zero for o in outs])

    return kernel


def circuit_on_planes(
    circ: Circuit,
    input_ids: Sequence[Sequence[int]],
    operand_planes: Sequence[jax.Array],
    *,
    block_w: int = DEFAULT_BLOCK_W,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Execute a MAJ/NOT circuit on vertical-layout operands via Pallas.

    operand_planes[i]: (width_i, W) uint32.  Returns (n_outputs, W) uint32
    (one plane per circuit output bit).  W must be a multiple of block_w
    (callers pad; repro.kernels.ops handles it).
    """
    w_total = operand_planes[0].shape[-1]
    assert all(p.shape[-1] == w_total for p in operand_planes)
    bw = min(block_w, w_total)
    assert w_total % bw == 0, (w_total, bw)
    n_out = len(circ.outputs)

    kernel = _make_kernel(circ, tuple(tuple(ids) for ids in input_ids))
    in_specs = [
        pl.BlockSpec((p.shape[0], bw), lambda i: (0, i))
        for p in operand_planes
    ]
    out_spec = pl.BlockSpec((n_out, bw), lambda i: (0, i))
    fn = pl.pallas_call(
        kernel,
        grid=(w_total // bw,),
        in_specs=in_specs,
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct((n_out, w_total), jnp.uint32),
        interpret=resolve_interpret(interpret),
    )
    return fn(*[p.astype(jnp.uint32) for p in operand_planes])
