"""Sharding rules: 2-D (FSDP × TP) parameter layout + EP for MoE.

Mesh axes:
  single-pod: ("data", "model") = (16, 16)
  multi-pod : ("pod", "data", "model") = (2, 16, 16)

DATA = ("pod","data") — the combined FSDP/batch axes.  Every large matrix
is sharded BOTH ways: its "parallel" dim on `model` (tensor parallelism:
heads / ffn-hidden / vocab / experts) and the other dim on DATA (FSDP
storage sharding; GSPMD all-gathers just-in-time per layer under the
scan).  MoE expert stacks shard experts on `model` (expert parallelism).
Norm gains / scalar vectors replicate.

Every desired axis passes through a divisibility fit (`_fit`): if a dim
doesn't divide by the requested axis product, the rule degrades gracefully
(tuple → shorter tuple → replicated).  This is what lets ONE rule set
serve a batch-1 500k-decode cell and a batch-256 train cell, kv-head
counts below the TP degree, and hymba's 50 SSD heads, without per-arch
special cases.  Vocab dims are pre-padded in the model (config.vocab_padded).

These rules are pure functions path→PartitionSpec so the same tree serves
params, grads and both Adam moments; caches/batches have their own rule
sets.  All rules are exercised by every dry-run cell (launch/dryrun.py).
"""

from __future__ import annotations

import math
from typing import Any, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Axis = Union[None, str, Tuple[str, ...]]


def abstract_mesh(axis_sizes: Sequence[int], axis_names: Sequence[str]):
    """A device-free ``jax.sharding.AbstractMesh``: the sharding rules
    here only consume ``mesh.shape`` / ``mesh.axis_names``."""
    from jax.sharding import AbstractMesh

    return AbstractMesh(tuple(axis_sizes), tuple(axis_names))


def data_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def _axis_size(mesh: Mesh, axis: Axis) -> int:
    if axis is None:
        return 1
    if isinstance(axis, str):
        return mesh.shape[axis]
    return math.prod(mesh.shape[a] for a in axis)


def _fit(mesh: Mesh, dim: int, want: Axis) -> Axis:
    """Largest prefix of `want` whose size divides `dim` (None if none)."""
    if want is None:
        return None
    cands = [want]
    if isinstance(want, tuple):
        # try dropping leading axes: ('pod','data') -> ('data',)
        for i in range(1, len(want)):
            cands.append(want[i:])
    cands.append(None)
    for c in cands:
        if c is None:
            return None
        if dim % _axis_size(mesh, c) == 0:
            return c if not (isinstance(c, tuple) and len(c) == 1) else c[0]
    return None


def fit_spec(mesh: Mesh, shape: Sequence[int], *want: Axis) -> P:
    assert len(shape) == len(want), (shape, want)
    return P(*[_fit(mesh, d, w) for d, w in zip(shape, want)])


def _names(path) -> list:
    out = []
    for k in path:
        if hasattr(k, "key"):
            out.append(str(k.key))
        elif hasattr(k, "name"):
            out.append(str(k.name))
        elif hasattr(k, "idx"):
            out.append(str(k.idx))
    return out


# parents whose dense 'w' has its OUTPUT dim model-parallel
_COL_PARALLEL = {"q", "k", "v", "up", "gate", "in_proj_z", "in_proj_xbc",
                 "out", "frontend_proj"}
# parents whose dense 'w' has its INPUT dim model-parallel
_ROW_PARALLEL = {"o", "down", "out_proj"}
# tiny projections that replicate their output dim
_REPLICATED_OUT = {"in_proj_dt"}


def param_spec(path, leaf, mesh: Mesh) -> P:
    names = _names(path)
    DATA = data_axes(mesh)
    stacked = any(n in ("blocks", "enc_blocks") for n in names)
    pre: Tuple[Axis, ...] = (None,) if stacked else ()
    shape = leaf.shape[len(pre):]
    name = names[-1] if names else ""
    parent = names[-2] if len(names) > 1 else ""
    nd = len(shape)

    def fs(*want: Axis) -> P:
        return fit_spec(mesh, leaf.shape, *(pre + want))

    # int8-quantized weights: w_q follows the projection's 'w' rule; the
    # per-output-channel scale follows the weight's LAST-dim sharding
    if name in ("w_q", "scale"):
        proj = parent
        container = names[-3] if len(names) > 2 else ""
        if name == "scale":
            if proj in ("up", "gate") and (container == "moe" or nd == 2):
                return fs("model", None)          # (E, f)
            if proj == "down" and (container == "moe" or nd == 2):
                return fs("model", DATA)          # (E, d)
            if proj in _COL_PARALLEL:
                return fs("model")
            if proj in _ROW_PARALLEL:
                return fs(DATA)
            return fs(*([None] * nd))
        name, parent = (proj if nd == 3 else "w"), (container if nd == 3 else proj)

    if name == "emb":
        return fs("model", None)
    if name in ("g", "a_log", "d_skip", "dt_bias", "conv_b"):
        return fs(*([None] * nd))
    if name == "conv_w":
        return fs(None, "model")
    if name == "router":
        return fs(None, None)
    if parent == "moe" or nd == 3:
        # stacked expert weights (E, d, f) / (E, f, d): EP on model
        if name in ("up", "gate"):
            return fs("model", DATA, None)
        if name == "down":
            return fs("model", None, DATA)
        return fs("model", None, None)
    if nd == 2:
        if parent in _COL_PARALLEL:
            return fs(DATA, "model")
        if parent in _ROW_PARALLEL:
            return fs("model", DATA)
        if parent in _REPLICATED_OUT:
            return fs(DATA, None)
        return fs(*([None] * nd))
    if nd == 1:
        if parent in _COL_PARALLEL:
            return fs("model")
        return fs(None)
    return fs(*([None] * nd))


def param_spec_dp(path, leaf, mesh: Mesh) -> P:
    """Pure-FSDP (ZeRO-3) layout: no tensor parallelism — every param's
    largest dimension is sharded across ALL mesh axes; activations are
    batch-sharded across all axes too.

    Rationale (the small-model hillclimb): when d_model/TP-degree is tiny
    (seamless, internvl2, granite-moe), 2-D sharding turns every layer
    into sub-128 matmul shards plus per-layer TP collectives that dwarf
    compute; DP-only keeps matmuls whole and pays one gradient
    reduce-scatter per step.
    """
    names = _names(path)
    stacked = any(n in ("blocks", "enc_blocks") for n in names)
    pre: Tuple[Axis, ...] = (None,) if stacked else ()
    shape = leaf.shape[len(pre):]
    if not shape:
        return P(*pre)
    # embeddings / readout stay vocab-TP even under DP: ZeRO-3 would
    # re-gather the (often dominant) vocab table every step, while the
    # vocab-sharded form needs only an activation-sized psum
    name = names[-1] if names else ""
    parent = names[-2] if len(names) > 1 else ""
    if name == "emb":
        return fit_spec(mesh, leaf.shape, *(pre + ("model", None)))
    if parent == "out" and name in ("w", "w_q"):
        return fit_spec(mesh, leaf.shape, *(pre + (None, "model")))
    if parent == "out" and name == "scale":
        return fit_spec(mesh, leaf.shape, *(pre + ("model",)))
    ALL = tuple(mesh.axis_names)
    # shard the largest divisible dim over all axes (degrade via _fit)
    order = sorted(range(len(shape)), key=lambda i: -shape[i])
    want: list = [None] * len(shape)
    for i in order:
        ax = _fit(mesh, shape[i], ALL)
        if ax is not None and _axis_size(mesh, ax) == _axis_size(mesh, ALL):
            want[i] = ax
            break
    else:
        for i in order:                      # partial sharding fallback
            ax = _fit(mesh, shape[i], ALL)
            if ax is not None:
                want[i] = ax
                break
    return P(*(pre + tuple(want)))


def _strip_data_axes(spec: P, mesh: Mesh) -> P:
    """Replace DATA axes with replication (serve policy: weights stay
    resident, TP-sharded only — no per-step FSDP re-gathers at decode)."""
    drop = set(data_axes(mesh))

    def clean(s):
        if s is None:
            return None
        if isinstance(s, str):
            return None if s in drop else s
        kept = tuple(a for a in s if a not in drop)
        return kept if len(kept) > 1 else (kept[0] if kept else None)

    return P(*[clean(s) for s in spec])


def param_spec_dp2(path, leaf, mesh: Mesh) -> P:
    """ZeRO-2-style: small block weights fully REPLICATED (no per-layer
    re-gather in fwd/bwd), embeddings vocab-TP, optimizer state sharded
    (see opt_shardings).  Step pays one grad reduce + one param broadcast
    instead of 2× weight gathers + grad RS — a win when weights/chip are
    tiny (seamless: 0.35 GB replicated)."""
    names = _names(path)
    name = names[-1] if names else ""
    parent = names[-2] if len(names) > 1 else ""
    stacked = any(n in ("blocks", "enc_blocks") for n in names)
    pre: Tuple[Axis, ...] = (None,) if stacked else ()
    nd = leaf.ndim - len(pre)
    if name == "emb":
        return fit_spec(mesh, leaf.shape, *(pre + ("model", None)))
    if parent == "out" and name in ("w", "w_q"):
        return fit_spec(mesh, leaf.shape, *(pre + (None, "model")))
    return P(*(pre + (None,) * nd))


def param_shardings(params_like: Any, mesh: Mesh, policy: str = "2d") -> Any:
    spec_fn = {"dp": param_spec_dp, "dp2": param_spec_dp2}.get(policy, param_spec)

    def one(path, leaf):
        spec = spec_fn(path, leaf, mesh)
        if policy == "serve":
            spec = _strip_data_axes(spec, mesh)
        return NamedSharding(mesh, spec)

    return jax.tree_util.tree_map_with_path(one, params_like)


def opt_shardings(opt_state_like: Any, params_like: Any, mesh: Mesh,
                  policy: str = "2d") -> Any:
    """OptState(step, mu, nu): moments mirror the param layout — except
    under dp2 (ZeRO-2), where moments stay fully sharded while params
    replicate."""
    from repro.train.optimizer import OptState
    moment_policy = "dp" if policy == "dp2" else policy
    ps = param_shardings(params_like, mesh, moment_policy)
    rep = NamedSharding(mesh, P())
    return OptState(step=rep, mu=ps, nu=ps)


def batch_shardings(batch_like: Any, mesh: Mesh, policy: str = "2d") -> Any:
    DATA = (tuple(mesh.axis_names) if policy in ("dp", "dp2")
            else data_axes(mesh))

    def spec(path, leaf):
        want = (DATA,) + (None,) * (leaf.ndim - 1)
        return NamedSharding(mesh, fit_spec(mesh, leaf.shape, *want))

    return jax.tree_util.tree_map_with_path(spec, batch_like)


def cache_shardings(caches_like: Any, mesh: Mesh) -> Any:
    """Stacked caches (L, B, ...): batch on DATA, heads on model — with
    divisibility fallback (kv groups < TP degree shard head_dim instead)."""
    DATA = data_axes(mesh)

    def spec(path, leaf):
        names = _names(path)
        name = names[-1] if names else ""
        s = leaf.shape
        if name in ("k", "v"):                 # (L,B,S,G,hd)
            g_ax = _fit(mesh, s[3], "model")
            hd_ax = _fit(mesh, s[4], "model") if g_ax is None else None
            return NamedSharding(mesh, fit_spec(
                mesh, s, None, DATA, None, g_ax, hd_ax))
        if name in ("k_scale", "v_scale"):      # (L,B,S,G)
            g_ax = _fit(mesh, s[3], "model")
            return NamedSharding(mesh, fit_spec(mesh, s, None, DATA, None, g_ax))
        if name == "ssm":                       # (L,B,H,N,P)
            h_ax = _fit(mesh, s[2], "model")
            p_ax = _fit(mesh, s[4], "model") if h_ax is None else None
            return NamedSharding(mesh, fit_spec(
                mesh, s, None, DATA, h_ax, None, p_ax))
        if name == "conv":                      # (L,B,K-1,C)
            return NamedSharding(mesh, fit_spec(mesh, s, None, DATA, None, "model"))
        return NamedSharding(mesh, P(*([None] * leaf.ndim)))

    return jax.tree_util.tree_map_with_path(spec, caches_like)


def logits_sharding(mesh: Mesh, batch: int) -> NamedSharding:
    DATA = data_axes(mesh)
    return NamedSharding(mesh, fit_spec(mesh, (batch, 1 << 30), DATA, "model"))


def vector_sharding(mesh: Mesh, batch: int) -> NamedSharding:
    DATA = data_axes(mesh)
    return NamedSharding(mesh, fit_spec(mesh, (batch,), DATA))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())
