"""Mixture-of-Experts FFN with top-k routing (granite-moe, arctic).

Dense-einsum dispatch (capacity-less, "soft-drop" formulation): tokens ×
experts one-hot combine weights.  Expert weights live in a single stacked
(E, ...) tensor so expert parallelism is just a sharding rule on axis 0
(see repro.distributed.sharding).  The router's top-k comparison is a
*relational* SIMDRAM op class; with cfg.pum enabled the k=1 argmax mask
can be computed via bbop greater/max chains (demonstration path).

Aux load-balancing loss follows Switch/GShard: E·Σ_e f_e·p_e.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .layers import Params, dense_init

def moe_init(key, d: int, d_ff: int, n_experts: int, act: str, dtype) -> Params:
    kr, ku, kg, kd = jax.random.split(key, 4)
    import math
    std = 1.0 / math.sqrt(d)
    p = {
        "router": (jax.random.normal(kr, (d, n_experts), jnp.float32) * std).astype(jnp.float32),
        "up": (jax.random.normal(ku, (n_experts, d, d_ff), jnp.float32) * std).astype(dtype),
        "down": (jax.random.normal(kd, (n_experts, d_ff, d), jnp.float32)
                 * (1.0 / math.sqrt(d_ff))).astype(dtype),
    }
    if act == "swiglu":
        p["gate"] = (jax.random.normal(kg, (n_experts, d, d_ff), jnp.float32) * std).astype(dtype)
    return p


def moe_forward(
    p: Params, x: jax.Array, *, top_k: int, act: str
) -> Tuple[jax.Array, jax.Array]:
    """x (B,L,D) -> (out (B,L,D), aux_loss ())."""
    b, l, d = x.shape
    n_e = p["router"].shape[1]
    logits = (x.astype(jnp.float32) @ p["router"])          # (B,L,E)
    probs = jax.nn.softmax(logits, axis=-1)
    topv, topi = jax.lax.top_k(probs, top_k)                 # (B,L,K)
    topv = topv / jnp.clip(topv.sum(-1, keepdims=True), 1e-9)

    # combine weights (B,L,E): scatter top-k renormalized probs
    onehot = jax.nn.one_hot(topi, n_e, dtype=jnp.float32)    # (B,L,K,E)
    comb = jnp.einsum("blk,blke->ble", topv, onehot)

    # dense dispatch: every expert sees all tokens, masked-combined.
    # (dryrun/roofline-faithful: per-chip FLOPs match EP all-to-all dispatch
    # when experts are sharded; the hillclimb swaps this for real a2a.)
    from .quantized import effective_weight
    w_up = effective_weight(p["up"], x.dtype)
    w_down = effective_weight(p["down"], x.dtype)
    up = jnp.einsum("bld,edf->blef", x, w_up)
    if act == "swiglu":
        g = jnp.einsum("bld,edf->blef", x, effective_weight(p["gate"], x.dtype))
        h = jax.nn.silu(g) * up
    else:
        h = jax.nn.gelu(up)
    out = jnp.einsum("blef,efd->bled", h, w_down)
    out = jnp.einsum("bled,ble->bld", out, comb.astype(out.dtype))

    # aux load-balance loss
    frac_tokens = jnp.mean(onehot.sum(2), axis=(0, 1))       # f_e
    frac_probs = jnp.mean(probs, axis=(0, 1))                # p_e
    aux = n_e * jnp.sum(frac_tokens * frac_probs)
    return out, aux


def moe_forward_grouped(
    p: Params, x: jax.Array, *, top_k: int, act: str,
    capacity_factor: float = 1.25, ep_hints: bool = True,
) -> Tuple[jax.Array, jax.Array]:
    """Capacity-based dispatch (gather/scatter form): tokens are routed to
    per-expert buffers of size C = cf·T·K/E — the EP formulation whose
    per-expert matmuls shard over the 'model' axis without the E× FLOPs
    blowup of the dense path.

    ep_hints pins the expert buffers to P("model", …) so dispatch/combine
    lower to all-to-all-sized transfers instead of GSPMD replicating the
    (E, C, d) buffers per chip (the arctic hillclimb: collective bytes per
    layer drop from O(E·C·d) to O(T·d·k/chips)).  Overflowed tokens add 0
    via a weight-masked scatter-add (no ragged +1 slot — keeps every dim
    mesh-divisible).
    """
    from repro.distributed.hints import hint

    b, l, d = x.shape
    t = b * l
    n_e = p["router"].shape[1]
    xt = x.reshape(t, d)
    logits = xt.astype(jnp.float32) @ p["router"]
    probs = jax.nn.softmax(logits, axis=-1)
    topv, topi = jax.lax.top_k(probs, top_k)
    topv = topv / jnp.clip(topv.sum(-1, keepdims=True), 1e-9)

    cap = max(1, int(capacity_factor * t * top_k / n_e))
    flat_e = topi.reshape(-1)                                  # (T*K,)
    onehot = jax.nn.one_hot(flat_e, n_e, dtype=jnp.int32)      # (T*K,E)
    pos = (jnp.cumsum(onehot, axis=0) * onehot).sum(-1) - 1    # queue rank
    keep = pos < cap
    slot = jnp.where(keep, pos, cap - 1)                       # clamp overflow
    buf_idx = flat_e * cap + slot
    tok_idx = jnp.repeat(jnp.arange(t), top_k)

    # dispatch: scatter-ADD with overflow contributions zeroed — kept slots
    # are written exactly once (queue ranks are unique per expert)
    payload = xt[tok_idx] * keep[:, None].astype(xt.dtype)
    buf = jnp.zeros((n_e * cap, d), xt.dtype)
    buf = buf.at[buf_idx].add(payload)
    eb = buf.reshape(n_e, cap, d)
    if ep_hints:
        eb = hint(eb, "model", None, None)

    from .quantized import effective_weight
    up = jnp.einsum("ecd,edf->ecf", eb, effective_weight(p["up"], eb.dtype))
    if act == "swiglu":
        g = jnp.einsum("ecd,edf->ecf", eb, effective_weight(p["gate"], eb.dtype))
        h = jax.nn.silu(g) * up
    else:
        h = jax.nn.gelu(up)
    eout = jnp.einsum("ecf,efd->ecd", h, effective_weight(p["down"], eb.dtype))
    if ep_hints:
        eout = hint(eout, "model", None, None)
    eout = eout.reshape(n_e * cap, d)

    w = (topv.reshape(-1) * keep).astype(eout.dtype)
    out = jnp.zeros((t, d), eout.dtype)
    out = out.at[tok_idx].add(eout[buf_idx] * w[:, None])

    frac_tokens = jnp.mean(jax.nn.one_hot(topi, n_e, dtype=jnp.float32).sum(1), axis=0)
    aux = n_e * jnp.sum(frac_tokens * jnp.mean(probs, axis=0))
    return out.reshape(b, l, d), aux


def _grouped_local(p, xt, *, top_k, act, cap, e_lo, e_loc):
    """Token dispatch restricted to experts [e_lo, e_lo+e_loc) with LOCAL
    expert weights p (e_loc static; e_lo may be a traced axis_index).
    Tokens routed elsewhere contribute zero."""
    from .quantized import effective_weight

    t, d = xt.shape
    n_e = p["router"].shape[1]
    logits = xt.astype(jnp.float32) @ p["router"]
    probs = jax.nn.softmax(logits, axis=-1)
    topv, topi = jax.lax.top_k(probs, top_k)
    topv = topv / jnp.clip(topv.sum(-1, keepdims=True), 1e-9)

    flat_e = topi.reshape(-1)
    mine = (flat_e >= e_lo) & (flat_e < e_lo + e_loc)
    local_e = jnp.clip(flat_e - e_lo, 0, e_loc - 1)
    onehot = jax.nn.one_hot(local_e, e_loc, dtype=jnp.int32) * mine[:, None]
    pos = (jnp.cumsum(onehot, axis=0) * onehot).sum(-1) - 1
    keep = mine & (pos < cap)
    slot = jnp.where(keep, pos, cap - 1)
    buf_idx = local_e * cap + slot
    tok_idx = jnp.repeat(jnp.arange(t), top_k)

    payload = xt[tok_idx] * keep[:, None].astype(xt.dtype)
    buf = jnp.zeros((e_loc * cap, d), xt.dtype).at[buf_idx].add(payload)
    eb = buf.reshape(e_loc, cap, d)

    up = jnp.einsum("ecd,edf->ecf", eb, effective_weight(p["up"], eb.dtype))
    if act == "swiglu":
        g = jnp.einsum("ecd,edf->ecf", eb, effective_weight(p["gate"], eb.dtype))
        h = jax.nn.silu(g) * up
    else:
        h = jax.nn.gelu(up)
    eout = jnp.einsum("ecf,efd->ecd", h,
                      effective_weight(p["down"], eb.dtype)).reshape(e_loc * cap, d)

    w = (topv.reshape(-1) * keep).astype(eout.dtype)
    out = jnp.zeros((t, d), eout.dtype)
    out = out.at[tok_idx].add(eout[buf_idx] * w[:, None])

    frac_tokens = jnp.mean(jax.nn.one_hot(topi, n_e, dtype=jnp.float32).sum(1), axis=0)
    aux = n_e * jnp.sum(frac_tokens * jnp.mean(probs, axis=0))
    return out, aux


def moe_forward_ep(
    p: Params, x: jax.Array, *, top_k: int, act: str,
    capacity_factor: float = 1.25,
) -> Tuple[jax.Array, jax.Array]:
    """Expert parallelism via shard_map over the ambient mesh.

    Key idea: inside a TP block the activations are (logically) replicated
    across the `model` axis, so each model-rank can dispatch the SAME
    token set to its own E/TP experts with **zero communication**, compute
    locally, and emit a partial (T,d) output that a single psum over
    `model` combines.  Collectives per layer: one bf16 psum of the token
    activations — ~100× less than GSPMD's replicate-the-buffers fallback
    on arctic-480b (see EXPERIMENTS.md §Perf).

    Falls back to `moe_forward_grouped` when no mesh with a `model` axis
    is ambient (unit tests / single-host runs).
    """
    from jax.sharding import PartitionSpec as P
    from repro.distributed.hints import _ambient_mesh

    mesh = _ambient_mesh()
    if mesh is None or "model" not in mesh.axis_names:
        return moe_forward_grouped(p, x, top_k=top_k, act=act,
                                   capacity_factor=capacity_factor)
    b, l, d = x.shape
    n_e = p["router"].shape[1]
    tp = mesh.shape["model"]
    if n_e % tp != 0:
        return moe_forward_grouped(p, x, top_k=top_k, act=act,
                                   capacity_factor=capacity_factor)
    DATA = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    batch_ok = b % max(1, _prod(mesh.shape[a] for a in DATA)) == 0 if DATA else True
    bspec = DATA if (DATA and batch_ok) else None

    e_loc = n_e // tp
    t_loc = (b // max(1, _prod(mesh.shape[a] for a in DATA))
             if bspec else b) * l
    cap = max(1, int(capacity_factor * t_loc * top_k / n_e))

    def local_fn(router, up, gate, down, x_loc):
        rank = jax.lax.axis_index("model")
        p_loc = {"router": router, "up": up, "down": down}
        if gate is not None:
            p_loc["gate"] = gate
        bl, ll, dd = x_loc.shape
        out, aux = _grouped_local(
            p_loc, x_loc.reshape(bl * ll, dd), top_k=top_k, act=act,
            cap=cap, e_lo=rank * e_loc, e_loc=e_loc)
        out = jax.lax.psum(out, "model")
        aux = jax.lax.pmean(aux, "model")
        return out.reshape(bl, ll, dd), aux

    has_gate = "gate" in p
    in_specs = (
        P(None, None),                      # router replicated
        P("model", None, None),             # up   (E on model)
        P("model", None, None) if has_gate else None,
        P("model", None, None),             # down
        P(bspec, None, None),               # x
    )
    fn = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=in_specs,
        out_specs=(P(bspec, None, None), P()),
        check_vma=False,
    )
    gate = p.get("gate")
    # weights may be quantized dicts; shard_map specs must match pytrees
    def spec_like(w, spec):
        if isinstance(w, dict):
            return {k: spec if k == "w_q" else P("model", None) for k in w}
        return spec

    if any(isinstance(p[k], dict) for k in ("up", "down")):
        in_specs = (
            P(None, None),
            spec_like(p["up"], P("model", None, None)),
            spec_like(gate, P("model", None, None)) if has_gate else None,
            spec_like(p["down"], P("model", None, None)),
            P(bspec, None, None),
        )
        fn = jax.shard_map(local_fn, mesh=mesh, in_specs=in_specs,
                           out_specs=(P(bspec, None, None), P()),
                           check_vma=False)
    return fn(p["router"], p["up"], gate, p["down"], x)


def _prod(it):
    out = 1
    for v in it:
        out *= v
    return out
