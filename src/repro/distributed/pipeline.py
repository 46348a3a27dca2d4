"""GPipe-style pipeline parallelism over a mesh axis (SPMD, shard_map).

Stages are contiguous layer groups whose stacked params shard over the
pipeline axis (one stage per rank).  The schedule is the classic GPipe
fill/drain: ``n_ticks = n_micro + n_stages − 1``; every rank computes every
tick (bubble compute is wasted but SPMD-uniform), activations hop one rank
per tick via ``ppermute``.  Differentiable end-to-end (ppermute has a
transpose rule), so ``jax.grad`` yields the reverse-schedule backward pass.

This complements the GSPMD DP/TP/EP modes: for very deep models on
multi-pod meshes, sharding layers over the ``pod`` axis replaces the
cross-pod FSDP all-gathers with point-to-point activation hops
(n_micro·(S−1) sends of one microbatch activation each — independent of
parameter count).  Used by tests/test_pipeline.py (8 virtual hosts) and
available to the dry-run via layers-over-pod configs.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def gpipe(
    stage_fn: Callable,
    stacked_params,
    x: jax.Array,
    *,
    mesh: Mesh,
    axis: str = "pod",
    n_micro: int = 4,
):
    """Run ``stage_fn(stage_params, h) -> h`` as a pipeline over `axis`.

    stacked_params: pytree with leading dim = n_stages (sharded over axis).
    x: (B, ...) batch input (replicated over `axis`); B % n_micro == 0.
    Returns the pipeline output (B, ...), replicated over `axis`.
    """
    n_stages = mesh.shape[axis]
    b = x.shape[0]
    assert b % n_micro == 0, (b, n_micro)
    mb = b // n_micro

    def device_fn(params_stage, x_full):
        # params_stage: this rank's stage params (leading dim 1 -> squeeze)
        params_stage = jax.tree.map(lambda t: t[0], params_stage)
        rank = jax.lax.axis_index(axis)
        stream = x_full.reshape(n_micro, mb, *x_full.shape[1:])
        n_ticks = n_micro + n_stages - 1

        def tick(recv, t):
            mb_idx = jnp.clip(t, 0, n_micro - 1)
            x_in = jnp.where(rank == 0, stream[mb_idx], recv)
            y = stage_fn(params_stage, x_in)
            # hop: rank i -> i+1 (rank 0 receives zeros next tick)
            sent = jax.lax.ppermute(
                y, axis, [(i, i + 1) for i in range(n_stages - 1)])
            return sent, y

        recv0 = jnp.zeros((mb, *x_full.shape[1:]), x_full.dtype)
        _, ys = jax.lax.scan(tick, recv0, jnp.arange(n_ticks))
        # last rank's outputs for tick t belong to microbatch t-(S-1)
        outs = ys[n_stages - 1:]                       # (n_micro, mb, ...)
        out = outs.reshape(b, *x_full.shape[1:])
        # broadcast the last rank's result to everyone (cheap for demos;
        # production keeps loss computation on the last stage instead)
        out = jax.lax.psum(
            jnp.where(rank == n_stages - 1, out, jnp.zeros_like(out)), axis)
        return out

    fn = jax.shard_map(
        device_fn, mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P(),
        check_vma=False,
    )
    return fn(stacked_params, x)


def split_stages(stacked_layer_params, n_stages: int):
    """(L, ...)-stacked layer params -> (S, L/S, ...) stage-stacked."""
    def re(t):
        l = t.shape[0]
        assert l % n_stages == 0, (l, n_stages)
        return t.reshape(n_stages, l // n_stages, *t.shape[1:])
    return jax.tree.map(re, stacked_layer_params)
