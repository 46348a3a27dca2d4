"""Pallas TPU kernels for SIMDRAM's compute hot-spots.

  bitplane_ops.py      fused MAJ/NOT-circuit execution on bit-planes
  transpose_kernel.py  32×32 SWAR bit transpose (the transposition unit)
  bitserial_matmul.py  binary popcount-matmul (bit-serial NN engine)
  ops.py               jit'd wrappers + padding + dispatch
  ref.py               pure-jnp oracles for all of the above

Every kernel takes ``interpret=None``, which compiles it with Mosaic on
an accelerator and runs the Pallas interpreter on the CPU (where the
test-suite validates them); BlockSpecs target TPU v5e VMEM (see
per-module budget notes).
"""

from typing import Optional


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """``None`` picks from the default backend: interpret on the CPU,
    compiled everywhere else.  An explicit bool wins (compile rehearsals
    for a described TPU pass ``False`` from a CPU process)."""
    if interpret is not None:
        return interpret
    import jax
    return jax.default_backend() == "cpu"
