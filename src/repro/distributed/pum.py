"""Multi-device PuM execution: bank and chip axes on real device meshes.

SIMDRAM's headline scaling knob is bank count — 16 banks replaying one
broadcast command stream reach 88× CPU throughput — and banks share
*nothing*: each owns its subarray states and (since PR 2) its own stacked
command tables.  That makes the chip-level replay embarrassingly parallel
along the bank axis, so the stacked

    states: (n_banks, n_subarrays, n_rows, n_words)
    tables: (n_banks, n_subarrays, n_cmds, 13)

arrays ``shard_map`` over a 1-D ``("data",)`` mesh: every device replays
its local bank slabs with exactly the same scan interpreter the
single-device path vmaps (:func:`repro.core.control_unit.chip_replay`),
so the two executors are bit-exact by construction — the paper's
multi-bank parallelism mapped onto real accelerator parallelism.

One level up, chips on a memory channel share nothing either (PULSAR's
scaling argument: the per-chip replay path is untouched; only the outer
dispatch widens), so the channel-level stack

    states: (n_chips, n_banks, n_subarrays, n_rows, n_words)
    tables: (n_chips, n_banks, n_subarrays, n_cmds, 13)

``shard_map``s over a 2-D ``("channel", "data")`` mesh — chip slabs
split across ``channel``, each chip's bank slabs across ``data`` — with
the same bit-exact jitted vmap fallback
(:func:`repro.core.control_unit.channel_replay`) on small hosts.

Divisibility follows :mod:`repro.distributed.sharding`'s ``fit_spec``
discipline: if an axis count doesn't divide the device count the spec
degrades to replication and the executor falls back to the jitted
vmap path (also used on single-device hosts).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.control_unit import (channel_batched_interpreter,
                                     channel_replay,
                                     chip_batched_interpreter, chip_replay,
                                     faulty_channel_batched_interpreter,
                                     faulty_channel_replay,
                                     faulty_chip_batched_interpreter,
                                     faulty_chip_replay,
                                     rank_batched_interpreter, rank_replay)

from .sharding import fit_spec

#: how each tier's stacked ``(units..., n_subarrays, rows, words)`` states
#: and ``(units..., n_subarrays, n_cmds, 13)`` tables split over its mesh
BANK_SPEC = P("data", None, None, None)
CHIP_SPEC = P("channel", "data", None, None, None)
CHANNEL_SPEC = P("rank", "channel", "data", None, None, None)


def _note_executor(kind: str, mesh: Optional[Mesh], sharded: bool) -> None:
    """Record which replay executor a tier got (shard_map vs the vmap
    fallback, and over how many devices) in the active trace, so a
    Perfetto timeline says how the replay actually partitioned."""
    from repro.core.telemetry import active_tracer
    tr = active_tracer()
    if tr is not None:
        tr.event("pum.executor", cat="plan", kind=kind, sharded=sharded,
                 devices=int(mesh.devices.size) if mesh is not None else 1)


def pum_mesh(n_banks: int, devices: Optional[Sequence] = None) -> Optional[Mesh]:
    """1-D ``("data",)`` mesh over the largest device prefix whose size
    divides ``n_banks`` (equal bank slabs per device).  ``None`` when
    only a single device would participate — the caller should use the
    vmap fallback instead of paying shard_map overhead for nothing."""
    devs = list(devices if devices is not None else jax.devices())
    size = max((d for d in range(1, len(devs) + 1) if n_banks % d == 0),
               default=1)
    if size <= 1:
        return None
    return Mesh(np.array(devs[:size]), ("data",))


@dataclass(frozen=True)
class ChipExecutor:
    """A compiled chip-replay callable plus how it partitions.

    ``run(states, tables)`` returns the executed states asynchronously
    (a jitted call either way).  ``spec`` is how a shard_map ``run``
    splits its inputs over ``mesh``, bank slabs on different devices;
    ``None`` when one device vmaps them.  :meth:`place` is the one way a
    stacked host array reaches the device.
    """

    run: Callable
    mesh: Optional[Mesh]
    spec: Optional[P] = None

    @property
    def sharded(self) -> bool:
        """Whether bank slabs execute on different devices."""
        return self.spec is not None

    @property
    def sharding(self) -> Optional[NamedSharding]:
        """Where ``run`` reads its inputs, or ``None`` for the default
        device."""
        if self.spec is None:
            return None
        return NamedSharding(self.mesh, self.spec)

    def place(self, host: np.ndarray) -> jax.Array:
        """Copy a stacked host array to where ``run`` reads it: each
        unit slab straight onto the device that replays it, so nothing
        is resharded on the device; one device takes it whole."""
        if self.spec is None:
            return jnp.asarray(host)
        return jax.device_put(host, self.sharding)

    def describe(self) -> dict:
        """Flat summary for telemetry / benchmark artifacts."""
        return {
            "sharded": bool(self.sharded),
            "devices": int(self.mesh.devices.size) if self.mesh is not None
            else 1,
            "axes": list(self.mesh.axis_names) if self.mesh is not None
            else [],
        }


def make_chip_executor(
    n_banks: int,
    mesh: Optional[Mesh] = None,
    use_shard_map: Optional[bool] = None,
) -> ChipExecutor:
    """Build the chip's replay executor.

    ``use_shard_map``: ``None`` auto-selects (shard_map whenever a
    multi-device mesh fits the bank axis), ``True`` requires it (raises
    if no mesh fits — the CI forced-device path uses this to guarantee
    the partitioned executor is actually exercised), ``False`` forces
    the single-device vmap fallback (the bit-exactness reference).
    """
    if use_shard_map is False:
        _note_executor("chip", None, False)
        return ChipExecutor(chip_batched_interpreter(), None)
    if mesh is None:
        mesh = pum_mesh(n_banks)
    has_data = mesh is not None and "data" in tuple(mesh.axis_names)
    spec = fit_spec(mesh, (n_banks,), "data") if has_data else P(None)
    fits = has_data and spec[0] == "data" and mesh.shape["data"] > 1
    if not fits:
        if use_shard_map:
            raise ValueError(
                f"shard_map requested but no multi-device mesh fits "
                f"n_banks={n_banks} (devices={jax.device_count()})")
        _note_executor("chip", mesh, False)
        return ChipExecutor(chip_batched_interpreter(), mesh)
    _note_executor("chip", mesh, True)
    return ChipExecutor(_sharded_executor(mesh), mesh, BANK_SPEC)


@functools.lru_cache(maxsize=None)
def _sharded_executor(mesh: Mesh) -> Callable:
    """One jitted shard_map executor per mesh — every chip on the same
    mesh shares it, so jit's shape cache (and the compiled executables)
    amortize across chips exactly like the vmap fallback's lru_cache."""
    return jax.jit(jax.shard_map(
        chip_replay, mesh=mesh,
        in_specs=(BANK_SPEC, BANK_SPEC), out_specs=BANK_SPEC,
        check_vma=False))


def make_faulty_chip_executor(
    n_banks: int,
    mesh: Optional[Mesh] = None,
    use_shard_map: Optional[bool] = None,
) -> ChipExecutor:
    """Fault-injected twin of :func:`make_chip_executor`: the callable
    takes ``(states, tables, keys, stuck0, stuck1, dead, p_flip)`` and
    returns ``(executed states, per-subarray flip counts)``.  The fault
    operands are just more per-bank arrays, so they shard over the same
    ``data`` axis as the state slabs and the mesh-selection logic is
    identical."""
    if use_shard_map is False:
        _note_executor("chip.faulty", None, False)
        return ChipExecutor(faulty_chip_batched_interpreter(), None)
    if mesh is None:
        mesh = pum_mesh(n_banks)
    has_data = mesh is not None and "data" in tuple(mesh.axis_names)
    spec = fit_spec(mesh, (n_banks,), "data") if has_data else P(None)
    fits = has_data and spec[0] == "data" and mesh.shape["data"] > 1
    if not fits:
        if use_shard_map:
            raise ValueError(
                f"shard_map requested but no multi-device mesh fits "
                f"n_banks={n_banks} (devices={jax.device_count()})")
        _note_executor("chip.faulty", mesh, False)
        return ChipExecutor(faulty_chip_batched_interpreter(), mesh)
    _note_executor("chip.faulty", mesh, True)
    return ChipExecutor(_sharded_faulty_executor(mesh), mesh, BANK_SPEC)


@functools.lru_cache(maxsize=None)
def _sharded_faulty_executor(mesh: Mesh) -> Callable:
    unit2 = P("data", None, None)      # keys (banks, subs, 2), masks (banks, subs, words)
    unit1 = P("data", None)            # dead flags / flip counts (banks, subs)
    return jax.jit(jax.shard_map(
        faulty_chip_replay, mesh=mesh,
        in_specs=(BANK_SPEC, BANK_SPEC, unit2, unit2, unit2, unit1, P()),
        out_specs=(BANK_SPEC, unit1),
        check_vma=False))


# ---------------------------------------------------------------------------
# channel level: chips × banks on a 2-D ("channel", "data") mesh
# ---------------------------------------------------------------------------

def channel_mesh(n_chips: int, n_banks: int,
                 devices: Optional[Sequence] = None) -> Optional[Mesh]:
    """2-D ``("channel", "data")`` mesh for a channel's chip × bank grid.

    Picks the largest device grid ``(ch, da)`` with ``ch | n_chips`` and
    ``da | n_banks`` (equal chip slabs per ``channel`` row, equal bank
    slabs per ``data`` column), preferring to spend devices on the
    ``channel`` axis at equal total — chips are the outer scaling knob
    this tier adds.  ``None`` when only a single device would
    participate: the caller should use the vmap fallback instead of
    paying shard_map overhead for nothing."""
    devs = list(devices if devices is not None else jax.devices())
    best = (1, 1)
    for ch in range(1, len(devs) + 1):
        if n_chips % ch:
            continue
        da = max((d for d in range(1, len(devs) // ch + 1)
                  if n_banks % d == 0), default=1)
        if (ch * da, ch) > (best[0] * best[1], best[0]):
            best = (ch, da)
    ch, da = best
    if ch * da <= 1:
        return None
    return Mesh(np.array(devs[: ch * da]).reshape(ch, da),
                ("channel", "data"))


@dataclass(frozen=True)
class ChannelExecutor(ChipExecutor):
    """A compiled channel-replay callable plus how it partitions.

    ``run(states, tables)`` returns the executed (n_chips, n_banks,
    n_subarrays, n_rows, n_words) states asynchronously (a jitted call
    either way); ``sharded`` tells whether chip/bank slabs execute on
    different devices (2-D shard_map) or one device vmaps the whole
    stack.
    """


def make_channel_executor(
    n_chips: int,
    n_banks: int,
    mesh: Optional[Mesh] = None,
    use_shard_map: Optional[bool] = None,
) -> ChannelExecutor:
    """Build the channel's replay executor.

    ``use_shard_map``: ``None`` auto-selects (shard_map whenever a
    multi-device ``("channel", "data")`` mesh fits the chip × bank
    grid), ``True`` requires it (raises if no mesh fits — the CI
    forced-device path uses this to guarantee the 2-D partitioned
    executor is actually exercised), ``False`` forces the single-device
    vmap fallback (the bit-exactness reference).
    """
    if use_shard_map is False:
        _note_executor("channel", None, False)
        return ChannelExecutor(channel_batched_interpreter(), None)
    if mesh is None:
        mesh = channel_mesh(n_chips, n_banks)
    has_axes = mesh is not None and {"channel", "data"} <= set(
        mesh.axis_names)
    spec = (fit_spec(mesh, (n_chips, n_banks), "channel", "data")
            if has_axes else P(None, None))
    fits = (has_axes and spec[0] == "channel" and spec[1] == "data"
            and mesh.devices.size > 1)
    if not fits:
        if use_shard_map:
            raise ValueError(
                f"shard_map requested but no multi-device (channel, data) "
                f"mesh fits n_chips={n_chips} × n_banks={n_banks} "
                f"(devices={jax.device_count()})")
        _note_executor("channel", mesh, False)
        return ChannelExecutor(channel_batched_interpreter(), mesh)
    _note_executor("channel", mesh, True)
    return ChannelExecutor(_sharded_channel_executor(mesh), mesh, CHIP_SPEC)


@functools.lru_cache(maxsize=None)
def _sharded_channel_executor(mesh: Mesh) -> Callable:
    """One jitted 2-D shard_map executor per mesh — every channel on the
    same mesh shares it, exactly like the chip-level executor cache."""
    return jax.jit(jax.shard_map(
        channel_replay, mesh=mesh,
        in_specs=(CHIP_SPEC, CHIP_SPEC), out_specs=CHIP_SPEC,
        check_vma=False))


def make_faulty_channel_executor(
    n_chips: int,
    n_banks: int,
    mesh: Optional[Mesh] = None,
    use_shard_map: Optional[bool] = None,
) -> ChannelExecutor:
    """Fault-injected twin of :func:`make_channel_executor`: the callable
    takes ``(states, tables, keys, stuck0, stuck1, dead, p_flip)`` and
    returns ``(executed states, per-subarray flip counts)``, with the
    fault operands sharded over the same ``("channel", "data")`` grid as
    the chip/bank slabs."""
    if use_shard_map is False:
        _note_executor("channel.faulty", None, False)
        return ChannelExecutor(faulty_channel_batched_interpreter(), None)
    if mesh is None:
        mesh = channel_mesh(n_chips, n_banks)
    has_axes = mesh is not None and {"channel", "data"} <= set(
        mesh.axis_names)
    spec = (fit_spec(mesh, (n_chips, n_banks), "channel", "data")
            if has_axes else P(None, None))
    fits = (has_axes and spec[0] == "channel" and spec[1] == "data"
            and mesh.devices.size > 1)
    if not fits:
        if use_shard_map:
            raise ValueError(
                f"shard_map requested but no multi-device (channel, data) "
                f"mesh fits n_chips={n_chips} × n_banks={n_banks} "
                f"(devices={jax.device_count()})")
        _note_executor("channel.faulty", mesh, False)
        return ChannelExecutor(faulty_channel_batched_interpreter(), mesh)
    _note_executor("channel.faulty", mesh, True)
    return ChannelExecutor(_sharded_faulty_channel_executor(mesh), mesh,
                           CHIP_SPEC)


@functools.lru_cache(maxsize=None)
def _sharded_faulty_channel_executor(mesh: Mesh) -> Callable:
    unit2 = P("channel", "data", None, None)   # keys / stuck masks
    unit1 = P("channel", "data", None)         # dead flags / flip counts
    return jax.jit(jax.shard_map(
        faulty_channel_replay, mesh=mesh,
        in_specs=(CHIP_SPEC, CHIP_SPEC, unit2, unit2, unit2, unit1, P()),
        out_specs=(CHIP_SPEC, unit1),
        check_vma=False))


# ---------------------------------------------------------------------------
# rank level: channels × chips × banks on a 3-D ("rank", "channel", "data") mesh
# ---------------------------------------------------------------------------

def rank_mesh(n_channels: int, n_chips: int, n_banks: int,
              devices: Optional[Sequence] = None) -> Optional[Mesh]:
    """3-D ``("rank", "channel", "data")`` mesh for a rank's channel ×
    chip × bank grid.

    Picks the largest device grid ``(ra, ch, da)`` with ``ra |
    n_channels``, ``ch | n_chips`` and ``da | n_banks`` (equal channel
    slabs per ``rank`` plane, equal chip slabs per ``channel`` row,
    equal bank slabs per ``data`` column), preferring to spend devices
    on the outer axes at equal total — channels are the outermost
    scaling knob this tier adds.  ``None`` when only a single device
    would participate: the caller should use the vmap fallback instead
    of paying shard_map overhead for nothing."""
    devs = list(devices if devices is not None else jax.devices())
    best = (1, 1, 1)
    for ra in range(1, len(devs) + 1):
        if n_channels % ra:
            continue
        for ch in range(1, len(devs) // ra + 1):
            if n_chips % ch:
                continue
            da = max((d for d in range(1, len(devs) // (ra * ch) + 1)
                      if n_banks % d == 0), default=1)
            cand = (ra, ch, da)
            if ((ra * ch * da, ra, ch)
                    > (best[0] * best[1] * best[2], best[0], best[1])):
                best = cand
    ra, ch, da = best
    if ra * ch * da <= 1:
        return None
    return Mesh(np.array(devs[: ra * ch * da]).reshape(ra, ch, da),
                ("rank", "channel", "data"))


def make_rank_executor(
    n_channels: int,
    n_chips: int,
    n_banks: int,
    mesh: Optional[Mesh] = None,
    use_shard_map: Optional[bool] = None,
) -> ChannelExecutor:
    """Build the rank's replay executor (the :class:`ChannelExecutor`
    shape fits unchanged — ``run(states, tables)`` over one more leading
    axis).

    ``use_shard_map``: ``None`` auto-selects (shard_map whenever a
    multi-device ``("rank", "channel", "data")`` mesh fits the channel ×
    chip × bank grid), ``True`` requires it (raises if no mesh fits —
    the CI forced-device path uses this to guarantee the 3-D partitioned
    executor is actually exercised), ``False`` forces the single-device
    vmap fallback (the bit-exactness reference).
    """
    if use_shard_map is False:
        _note_executor("rank", None, False)
        return ChannelExecutor(rank_batched_interpreter(), None)
    if mesh is None:
        mesh = rank_mesh(n_channels, n_chips, n_banks)
    has_axes = mesh is not None and {"rank", "channel", "data"} <= set(
        mesh.axis_names)
    spec = (fit_spec(mesh, (n_channels, n_chips, n_banks),
                     "rank", "channel", "data")
            if has_axes else P(None, None, None))
    fits = (has_axes and spec[0] == "rank" and spec[1] == "channel"
            and spec[2] == "data" and mesh.devices.size > 1)
    if not fits:
        if use_shard_map:
            raise ValueError(
                f"shard_map requested but no multi-device "
                f"(rank, channel, data) mesh fits n_channels={n_channels} "
                f"× n_chips={n_chips} × n_banks={n_banks} "
                f"(devices={jax.device_count()})")
        _note_executor("rank", mesh, False)
        return ChannelExecutor(rank_batched_interpreter(), mesh)
    _note_executor("rank", mesh, True)
    return ChannelExecutor(_sharded_rank_executor(mesh), mesh, CHANNEL_SPEC)


@functools.lru_cache(maxsize=None)
def _sharded_rank_executor(mesh: Mesh) -> Callable:
    """One jitted 3-D shard_map executor per mesh — every rank on the
    same mesh shares it, exactly like the channel-level executor cache."""
    return jax.jit(jax.shard_map(
        rank_replay, mesh=mesh,
        in_specs=(CHANNEL_SPEC, CHANNEL_SPEC), out_specs=CHANNEL_SPEC,
        check_vma=False))
