"""Collective/overlap helpers on top of GSPMD.

GSPMD already schedules TP collectives; these helpers add the knobs the
perf pass iterates on:

- constrain(): with_sharding_constraint shorthand using mesh axis names —
  used to force activation layouts at block boundaries (e.g. sequence-
  parallel norms) so XLA doesn't round-trip through replicated form;
- async_allreduce_scan(): microbatch gradient scan in which each
  microbatch's psum is issued inside the scan body rather than once at
  the end — XLA overlaps the previous microbatch's all-reduce with the
  next microbatch's backward (the classic DP overlap);
- pod_psum_compressed(): shard_map wrapper running the int8 compressed
  all-reduce of repro.train.compression across the 'pod' axis only.
"""

from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def constrain(x: jax.Array, *spec) -> jax.Array:
    return jax.lax.with_sharding_constraint(x, P(*spec))


def sequence_parallel_norm(norm_fn: Callable, x: jax.Array,
                           seq_axis: str = "model") -> jax.Array:
    """Run a norm with the sequence dim sharded on `seq_axis` (SP): cheap
    elementwise work is distributed instead of replicated across TP ranks."""
    x = constrain(x, None, seq_axis, None)
    y = norm_fn(x)
    return constrain(y, None, seq_axis, None)


def async_allreduce_scan(grad_fn: Callable, params: Any, microbatches: Any,
                         axis_name: str) -> Any:
    """Gradient accumulation where each microbatch's contribution is
    psum'd inside the scan body (overlap-friendly schedule)."""

    def body(acc, mb):
        g = grad_fn(params, mb)
        g = jax.tree.map(lambda t: jax.lax.psum(t, axis_name), g)
        return jax.tree.map(jnp.add, acc, g), None

    zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    acc, _ = jax.lax.scan(body, zeros, microbatches)
    return acc


def pod_psum_compressed(mesh: Mesh, x: jax.Array) -> jax.Array:
    """int8-compressed all-reduce across pods (see train.compression)."""
    from repro.train.compression import compressed_psum

    if "pod" not in mesh.axis_names:
        return x
    inner_spec = P("pod", *([None] * (x.ndim - 1))) if x.shape[0] % mesh.shape["pod"] == 0 \
        else P(*([None] * x.ndim))

    fn = jax.shard_map(
        lambda t: compressed_psum(t, "pod"),
        mesh=mesh,
        in_specs=(inner_spec,),
        out_specs=inner_spec,
        check_vma=False,
    )
    return fn(x)
