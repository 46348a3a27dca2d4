"""Pallas kernel block-shape sweep (structural VMEM/roofline reasoning).

The "profile" here is structural — per config we report the VMEM working
set each program instance claims, its alignment to the 8×128 vreg grid,
and the analytic HBM↔VMEM traffic.  The wall time is one host-clocked
call including compilation; on the CPU the kernels are interpreted, so
there it is only a correctness-execution proxy.  The chosen
defaults (marked *) are the ones whose working set fits comfortably under
half of v5e's ~16 MiB VMEM (double-buffering headroom) with fully-aligned
lanes.

  python -m benchmarks.kernel_sweep
"""

from __future__ import annotations

import time

import jax.numpy as jnp
import numpy as np

VMEM_BYTES = 16 * 1024 * 1024


def sweep_bbop(op: str = "addition", n_bits: int = 8, lanes: int = 1 << 16):
    from repro.core.bitplane import _compiled_op
    from repro.kernels import ops as kops

    spec, circ, _ = _compiled_op(op, n_bits)
    live = circ.live_nodes()
    n_gates = sum(1 for n in live if circ.ops[n] in ("maj", "and", "or", "xor"))
    in_bits = sum(spec.operand_bits)
    out_bits = sum(spec.out_bits)
    rng = np.random.default_rng(0)
    xs = [jnp.asarray(rng.integers(0, 1 << w, size=lanes).astype(np.int32))
          for w in spec.operand_bits]

    print(f"# kernel_sweep/{op}/{n_bits}b: name,us_per_call,derived(vmem_kb)")
    for block_w in (128, 256, 512, 1024, 2048):
        # VMEM/instance: operand+output plane tiles + ~live-intermediate peak
        live_peak = min(n_gates, 16)  # fused bitwise chain, XLA reuses regs
        vmem = (in_bits + out_bits + live_peak) * block_w * 4
        aligned = block_w % 128 == 0
        t0 = time.perf_counter()
        kops.bbop_pallas(op, n_bits, *xs, block_w=block_w)
        us = (time.perf_counter() - t0) * 1e6
        star = "*" if block_w == 512 else " "
        print(f"bbop/{op}/bw{block_w}{star},{us:.0f},{vmem/1024:.0f}"
              f"  # aligned={aligned} instances={lanes//32//block_w}")


def sweep_bitserial(m: int = 128, k: int = 2048, n: int = 128):
    from repro.kernels import ops as kops

    rng = np.random.default_rng(1)
    a = jnp.asarray(rng.integers(0, 4, size=(m, k)).astype(np.int32))
    w = jnp.asarray(rng.integers(-2, 2, size=(k, n)).astype(np.int32))
    want = np.asarray(a) @ np.asarray(w)

    print("# kernel_sweep/bitserial_matmul: name,us_per_call,derived(vmem_kb)")
    for bm, bn, bk in ((32, 32, 16), (64, 64, 32), (128, 128, 64),
                       (128, 128, 16), (256, 128, 64)):
        vmem = (bm * bk + bk * bn + bm * bn) * 4
        mxu_aligned = bm % 8 == 0 and bn % 128 == 0
        t0 = time.perf_counter()
        got = kops.bitserial_matmul(a, w, 2, 2, a_signed=False, w_signed=True,
                                    bm=bm, bn=bn, bk=bk)
        us = (time.perf_counter() - t0) * 1e6
        assert np.array_equal(np.asarray(got), want)
        star = "*" if (bm, bn, bk) == (128, 128, 64) else " "
        print(f"bitserial/bm{bm}_bn{bn}_bk{bk}{star},{us:.0f},{vmem/1024:.0f}"
              f"  # lane_aligned={mxu_aligned}")


def sweep_transpose(lanes: int = 1 << 15):
    from repro.kernels.transpose_kernel import h2v_pallas

    rng = np.random.default_rng(2)
    v = jnp.asarray(rng.integers(0, 2**32, size=lanes, dtype=np.uint32))
    print("# kernel_sweep/transpose: name,us_per_call,derived(vmem_kb)")
    for bb in (1024, 2048, 4096, 8192):
        vmem = 2 * bb * 32 * 4
        t0 = time.perf_counter()
        h2v_pallas(v, block_b=bb)
        us = (time.perf_counter() - t0) * 1e6
        star = "*" if bb == 1024 else " "
        print(f"transpose/bb{bb}{star},{us:.0f},{vmem/1024:.0f}")


def sweep_bank(op: str = "addition", n_bits: int = 8, lanes: int = 4096):
    """Batched-interpreter working set vs subarray count: the bank engine
    stacks (n_sub, n_rows, n_words) states, so VMEM/instance grows
    linearly with n_sub while the command table is shared (read once)."""
    from repro.core.bank import (ROW_BUCKET, Bank, cached_table,
                                 random_operand_sets)

    spec, uprog, table = cached_table(op, n_bits)
    rows_alloc = -(-uprog.n_rows_total // ROW_BUCKET) * ROW_BUCKET
    print(f"# kernel_sweep/bank/{op}/{n_bits}b: name,us_per_call,"
          "derived(state_kb)")
    for n_sub in (1, 4, 16):
        bank = Bank(n_subarrays=n_sub)
        sets = random_operand_sets(spec, n_sub, lanes, seed=3)
        bank.execute_batch(op, n_bits, sets)      # compile + warm
        t0 = time.perf_counter()
        bank.execute_batch(op, n_bits, sets)
        us = (time.perf_counter() - t0) * 1e6
        state_kb = n_sub * rows_alloc * (lanes // 32) * 4 / 1024
        table_kb = table.size * 4 / 1024
        print(f"bank/{op}/sub{n_sub},{us:.0f},{state_kb:.0f}"
              f"  # shared_table_kb={table_kb:.1f}")


def main():
    sweep_bbop("addition", 8)
    sweep_bbop("multiplication", 8, lanes=1 << 14)
    sweep_bitserial()
    sweep_transpose()
    sweep_bank("addition", 8)
    print("# note: wall times include compilation (interpreted on the CPU); "
          "selection is by "
          "VMEM working set + 128-lane alignment (see module docstring)")


if __name__ == "__main__":
    main()
