"""Chip-level partitioned execution: N banks of M subarrays each.

The end-to-end SIMDRAM paper's control unit transparently allocates work
across *banks* — the 1/4/16-bank sweep that produces the headline 88×
CPU throughput runs one compute-enabled subarray per bank in lockstep.
This module reproduces that layer on top of the PR 2 fused bank engine:

  - a :class:`SimdramChip` owns ``n_banks`` :class:`~repro.core.bank.Bank`
    instances and stacks their wave slabs into one
    ``(n_banks, n_subarrays, n_rows, n_words)`` array — one *chip round*
    replays every bank's fused wave in a single
    :func:`repro.core.control_unit.chip_replay` call, ``shard_map``-ed
    over the ``data`` mesh axis when the host has multiple devices
    (:mod:`repro.distributed.pum`), vmapped over banks otherwise;
  - :meth:`SimdramChip.dispatch` is the partitioned front-end: the queue's
    Ref-connected producer→consumer chains are indivisible units (operand
    forwarding stays bank-local — planes never cross banks), and units
    are bin-packed onto banks longest-processing-time-first so modeled
    per-bank loads balance; within each bank the PR 4 cross-stage
    reordering scheduler takes over (``packing="ffd"``/``"greedy"``
    restore the PR 3/PR 2 packers), and each round's stacked command
    tables resolve from the compile-once device-resident
    :data:`repro.core.control_unit.TABLE_CACHE`;
  - :class:`ChipStats` extends :class:`~repro.core.bank.BankStats` with
    per-bank utilization, cross-bank imbalance, and the modeled-vs-
    measured latency pair (``latency_s`` vs ``wall_s``/``pack_wall_s``):
    a chip round models the *slowest bank's* wave — banks replay
    concurrently — while the wall-clock fields record what this host
    actually paid to pack and drain.

Bit-exactness: chip dispatch == sequential per-bank ``Bank.dispatch`` ==
the grouped baseline, property-tested in tests/test_chip.py and gated in
benchmarks/chip_scaling.py across all 16 ops in both MIG and AIG styles.
"""

from __future__ import annotations

import dataclasses
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .isa import DispatchGuard, check_cancel
from .bank import (Bank, BankStats, BbopInstr, Ref, _Slot,
                   _build_stacked_tables, horizontal_planes, plan_queue)
from .control_unit import CMD_WIDTH, TABLE_CACHE
from .costmodel import instr_cost_s
from .telemetry import active_tracer
from .timing import DDR4, DramConfig, chip_round_latency_s


@dataclass
class ChipStats(BankStats):
    """Aggregate cost model for everything a :class:`SimdramChip` ran.

    Inherited fields aggregate over all banks (``n_subarrays`` is the
    chip TOTAL, ``subarray_programs`` is flattened bank-major), with two
    semantic refinements: ``latency_s`` models banks replaying
    *concurrently* — each round charges its slowest bank's wave, which
    itself charges its longest constituent μProgram — and ``batches``
    counts per-bank waves while :attr:`rounds` counts stacked chip
    replays (one device round-trip each).  ``wall_s``/``pack_wall_s``
    are the measured host-side counterparts of ``latency_s`` — the
    modeled-vs-measured calibration pair benchmarks/chip_scaling.py
    tracks.
    """

    n_banks: int = 1
    rounds: int = 0                              # stacked chip replays
    bank_busy_s: np.ndarray = field(default=None)  # type: ignore

    # chip-tier additions to the inherited BankStats spec (see
    # repro.core.telemetry.spec_as_dict — keys merge across the MRO)
    _FIELD_SPEC = (
        ("n_banks", "int"),
        ("rounds", "int"),
        ("bank_busy_s", "float_list"),
        ("bank_programs", "int_list"),
        ("utilization", "float_list"),
        ("imbalance", "float"),
    )

    def __post_init__(self):
        super().__post_init__()
        if self.bank_busy_s is None:
            self.bank_busy_s = np.zeros(self.n_banks)

    @property
    def bank_programs(self) -> np.ndarray:
        """Instructions executed per bank (the scheduler's balance)."""
        return self.subarray_programs.reshape(self.n_banks, -1).sum(axis=1)

    @property
    def utilization(self) -> np.ndarray:
        """Per-bank busy fraction of the chip's modeled wall-clock."""
        if not self.latency_s:
            return np.zeros(self.n_banks)
        return self.bank_busy_s / self.latency_s

    @property
    def imbalance(self) -> float:
        """Slowest bank's busy time over the mean — 1.0 is a perfectly
        balanced schedule, n_banks is all work on one bank."""
        if not self.bank_busy_s.any():
            return 0.0
        return float(self.bank_busy_s.max() / self.bank_busy_s.mean())



def partition_queue(queue, active, lanes, n_banks: int,
                    cfg: DramConfig = DDR4, style: str = "mig",
                    allowed: Optional[Sequence[int]] = None
                    ) -> Dict[int, int]:
    """Assign instructions to banks: Ref-connected components are
    indivisible (forwarded planes never cross banks), weighted by
    :func:`repro.core.costmodel.instr_cost_s`, and bin-packed
    longest-processing-time-first onto the least-loaded bank.

    ``allowed`` restricts the candidate banks (the fault layer passes
    the non-blacklisted set so degraded dispatches repack around retired
    banks); ``None`` means all ``n_banks``."""
    parent = {i: i for i in active}

    def find(x):
        while parent[x] != x:
            parent[x] = parent[parent[x]]
            x = parent[x]
        return x

    act = set(active)
    for i in active:
        for o in queue[i].operands:
            if isinstance(o, Ref) and o.producer in act:
                parent[find(i)] = find(o.producer)
    comps: Dict[int, List[int]] = {}
    for i in active:
        comps.setdefault(find(i), []).append(i)
    cost = {
        root: sum(instr_cost_s(queue[i].op, queue[i].n_bits, lanes[i],
                               cfg, style) for i in members)
        for root, members in comps.items()
    }
    pool = list(range(n_banks)) if allowed is None else sorted(allowed)
    if not pool:
        raise ValueError("partition_queue: no banks allowed")
    load = np.zeros(n_banks)
    bank_of: Dict[int, int] = {}
    for root, members in sorted(
            comps.items(), key=lambda kv: (-cost[kv[0]], kv[0])):
        b = pool[int(np.argmin(load[pool]))]
        load[b] += cost[root]
        for i in members:
            bank_of[i] = b
    return bank_of


def sequential_dispatch(queue: Sequence[BbopInstr], n_banks: int = 4,
                        n_subarrays: int = 4, cfg: DramConfig = DDR4,
                        style: str = "mig", fuse: bool = True,
                        packing: str = "reorder"):
    """The no-chip baseline: the *same* bank partition a
    :class:`SimdramChip` would use, executed one bank at a time on
    separate :class:`~repro.core.bank.Bank` instances.

    Returns ``(results, banks)`` — results in queue order (bit-exactness
    reference for chip dispatch), and the per-bank ``Bank`` objects whose
    summed ``stats.latency_s`` is the serialized cost the chip's
    concurrent-banks model (max per round) improves on.
    """
    queue = list(queue)
    results: List = [None] * len(queue)
    banks = [Bank(n_subarrays=n_subarrays, cfg=cfg, style=style,
                  fuse=fuse, packing=packing) for _ in range(n_banks)]
    if not queue:
        return results, banks
    lanes, _, _ = plan_queue(queue, style)
    active = [i for i in range(len(queue)) if lanes[i] > 0]
    for i in range(len(queue)):
        if lanes[i] == 0:
            results[i] = banks[0]._empty_result(queue[i])
    bank_of = partition_queue(queue, active, lanes, n_banks, cfg, style)
    for b, bank in enumerate(banks):
        idxs = [i for i in active if bank_of[i] == b]
        if not idxs:
            continue
        remap = {qi: j for j, qi in enumerate(idxs)}
        sub = [
            dataclasses.replace(
                queue[qi],
                operands=tuple(
                    Ref(remap[o.producer], o.out) if isinstance(o, Ref)
                    else o
                    for o in queue[qi].operands))
            for qi in idxs
        ]
        for qi, out in zip(idxs, bank.dispatch(sub)):
            results[qi] = out
    return results, banks


def _devices(arr) -> int:
    """Devices a round's state lies on: 0 for a host array (the fault
    path heals its rounds on the host)."""
    return len(arr.sharding.device_set) if isinstance(arr, jax.Array) else 0


class SimdramChip:
    """``n_banks`` banks × ``n_subarrays`` subarrays, one stacked replay.

    All banks run the fused ``interp`` engine (heterogeneous waves,
    vertical operand forwarding); the chip stacks one wave per bank into
    each round.  ``mesh``/``use_shard_map`` control the executor (see
    :func:`repro.distributed.pum.make_chip_executor`): by default bank
    slabs shard over the ``data`` mesh axis whenever multiple devices
    fit, and fall back to a single-device vmap over banks otherwise —
    the two are bit-exact.
    """

    def __init__(self, n_banks: int = 4, n_subarrays: int = 4,
                 cfg: DramConfig = DDR4, style: str = "mig",
                 fuse_ratio: int = 32, packing: str = "reorder",
                 mesh=None, use_shard_map: Optional[bool] = None,
                 fault=None, fault_seed: Tuple[int, ...] = ()):
        if n_banks < 1:
            raise ValueError("n_banks must be >= 1")
        from repro.distributed.pum import make_chip_executor
        self.n_banks = n_banks
        self.n_subarrays = n_subarrays
        self.cfg = cfg
        self.style = style
        self.fault = fault if (fault is not None and fault.enabled) else None
        self.banks = [
            Bank(n_subarrays=n_subarrays, cfg=cfg, style=style,
                 engine="interp", fuse=True, fuse_ratio=fuse_ratio,
                 packing=packing, fault=self.fault,
                 fault_seed=tuple(fault_seed) + (b,))
            for b in range(n_banks)
        ]
        self.executor = make_chip_executor(n_banks, mesh=mesh,
                                           use_shard_map=use_shard_map)
        if self.fault is not None:
            from repro.distributed.pum import make_faulty_chip_executor
            self._faulty_executor = make_faulty_chip_executor(
                n_banks, mesh=mesh, use_shard_map=use_shard_map)
        else:
            self._faulty_executor = None
        self.stats = ChipStats(n_subarrays=n_banks * n_subarrays,
                               n_banks=n_banks)
        self._guard = DispatchGuard("SimdramChip")
        self._lane = "chip"          # telemetry track label
        for b, bank in enumerate(self.banks):
            bank._lane = f"bank{b}"

    # -- scheduling --------------------------------------------------------
    def _partition(self, queue, active, lanes) -> Dict[int, int]:
        allowed = ([b for b in range(self.n_banks)
                    if self.banks[b]._wave_capacity > 0]
                   if self.fault is not None else None)
        return partition_queue(queue, active, lanes, self.n_banks,
                               self.cfg, self.style, allowed=allowed)

    # -- dispatch ----------------------------------------------------------
    def dispatch(self, queue: Sequence[BbopInstr], cancel=None) -> List:
        """Drain a bbop queue across all banks.

        Args:
            queue: sequence of :class:`~repro.core.bank.BbopInstr`.
                ``Ref`` operands must point at earlier queue entries;
                Ref-connected chains are scheduled as indivisible units
                and never split across banks (forwarded bit-planes stay
                bank-local).

        Returns:
            One result per instruction, in queue order: an int64 array
            per output (tuple for multi-output ops), or
            :class:`~repro.core.bank.VerticalOperand` planes when the
            instruction set ``keep_vertical=True``.

        Costs accumulate in :attr:`stats` (a :class:`ChipStats`: modeled
        ``latency_s`` charges the slowest bank per round — banks replay
        concurrently — while ``wall_s``/``pack_wall_s`` record measured
        host time) and in each participating bank's own stats.  Host
        packing of round *k+1* overlaps the device replay of round *k*,
        exactly like the bank dispatcher.

        Bit-exactness guarantee: results are identical to
        :func:`sequential_dispatch` (same partition, one bank at a time)
        and to the grouped single-bank baseline, for every op, width,
        style, and executor (shard_map or vmap fallback) — gated in
        benchmarks/chip_scaling.py and tests/test_chip.py.

        With a :class:`~repro.core.fault.FaultModel` attached, the queue
        replicates across spare lanes and each chip round replays under
        fault injection with majority-vote detection, bounded retry, and
        bank/subarray blacklist-and-repack — see :mod:`repro.core.fault`.

        ``cancel`` (optional zero-arg callable) is polled at round
        boundaries; returning True aborts with
        :class:`~repro.core.isa.DispatchCancelled`.  Concurrent calls
        on one engine raise ``RuntimeError``
        (:class:`~repro.core.isa.DispatchGuard`)."""
        with self._guard:
            queue = list(queue)
            if self.fault is None or not queue:
                return self._dispatch_core(queue, cancel=cancel)
            from .fault import fault_guarded_dispatch
            return fault_guarded_dispatch(
                self.fault, self.stats.faults, queue,
                lambda q: self._dispatch_core(q, cancel=cancel),
                self._blacklist_units,
                lambda: sum(b._wave_capacity for b in self.banks),
                tier="chip",
                blacklist_snapshot=lambda: tuple(sorted(
                    (b, s) for b in range(self.n_banks)
                    for s in self.banks[b]._blacklist)))

    def _dispatch_core(self, queue: Sequence[BbopInstr],
                       cancel=None) -> List:
        queue = list(queue)
        results: List = [None] * len(queue)
        if not queue:
            return results           # clean no-op: stats stay zeroed
        tr = active_tracer()
        root = (tr.begin("chip.dispatch", cat="dispatch", lane=self._lane,
                         instrs=len(queue)) if tr is not None else None)
        t0 = time.perf_counter()
        self.stats.bbops += len(queue)
        sp = tr.begin("chip.plan", cat="plan") if tr is not None else None
        lanes, stage, needed = plan_queue(queue, self.style)
        if sp is not None:
            tr.end(sp)
        planes_cache: Dict[Tuple[int, int], np.ndarray] = {}
        active = []
        for i in range(len(queue)):
            if lanes[i] == 0:
                self.banks[0]._skip_zero_lane(
                    queue, i, needed, planes_cache, results)
            else:
                active.append(i)
        if not active:               # all-zero-lane queue: no replay
            self.stats.wall_s += time.perf_counter() - t0
            if root is not None:
                tr.end(root)
            return results

        sp = tr.begin("chip.schedule", cat="plan") if tr is not None else None
        bank_of = self._partition(queue, active, lanes)
        for i in active:
            self.banks[bank_of[i]].stats.bbops += 1
        waves_by_bank = [
            self.banks[b]._build_waves(
                queue, [i for i in active if bank_of[i] == b], stage, lanes)
            for b in range(self.n_banks)
        ]
        if sp is not None:
            tr.end(sp, banks=len(set(bank_of.values())))
        n_rounds = max(len(w) for w in waves_by_bank)
        pending: Optional[Tuple[List[Tuple[int, List[_Slot]]], jnp.ndarray]] = None
        for r in range(n_rounds):
            check_cancel(cancel, "chip round boundary")
            round_waves = [(b, waves_by_bank[b][r])
                           for b in range(self.n_banks)
                           if r < len(waves_by_bank[b])]
            if pending is not None:
                # stage barrier: a round forwarding planes from the
                # still-in-flight round drains it before packing
                in_flight = {e.qi for _, ents in pending[0] for e in ents}
                if any(isinstance(o, Ref) and o.producer in in_flight
                       for _, wave in round_waves
                       for i in wave for o in queue[i].operands):
                    self._harvest_round(queue, pending, planes_cache,
                                        needed, results, barrier=True)
                    pending = None
            entries_by_bank, fut = self._pack_round(
                queue, round_waves, lanes, planes_cache)
            if tr is not None:
                with tr.span("chip.account", cat="account"):
                    self._account_round(queue, entries_by_bank)
            else:
                self._account_round(queue, entries_by_bank)
            if pending is not None:
                # double buffering: round k harvests only after round
                # k+1 was packed and submitted
                self._harvest_round(queue, pending, planes_cache, needed,
                                    results, barrier=False)
            pending = (entries_by_bank, fut)
        if pending is not None:
            if tr is not None:
                with tr.span("chip.drain", cat="wait"):
                    jax.block_until_ready(pending[1])  # drain the pipeline
            else:
                jax.block_until_ready(pending[1])     # drain the pipeline
            self._harvest_round(queue, pending, planes_cache, needed,
                                results, barrier=True)
        self.stats.wall_s += time.perf_counter() - t0
        if root is not None:
            tr.end(root)
        return results

    def _round_dims(self, queue, round_waves, lanes) -> Tuple[int, int, int]:
        """(n_rows, n_cmds, cols) ONE chip round needs — the max of its
        participating banks' wave dims.  The channel-level dispatcher
        maxes these across chips so every chip's round packs into one
        stacked (n_chips, n_banks, n_subarrays, ...) super-round."""
        dims = [self.banks[b]._wave_dims(queue, wave, lanes)
                for b, wave in round_waves]
        return (max(d[0] for d in dims), max(d[1] for d in dims),
                max(d[2] for d in dims))

    def _pack_round_states(self, queue, round_waves, lanes, planes_cache,
                           n_rows: int, n_cmds: int, cols: int):
        """Pack one chip round's state slab at the given dims (NOP
        commands and zero rows are inert; idle banks stay all-NOP).

        Returns ``(states, bank_keys, entries_by_bank)`` — the raw
        (n_banks, n_subarrays, n_rows, n_words) array, the per-bank
        TABLE_CACHE wave keys, and the per-bank slot entries — without
        resolving tables or submitting a replay, so the channel
        dispatcher can stack several chips' rounds into one super-round
        replay.  Bank-level transpose savings/payments accrued while
        packing are mirrored into this chip's stats."""
        states = np.zeros(
            (self.n_banks, self.n_subarrays, n_rows, cols // 32), np.uint32)
        entries_by_bank: List[Tuple[int, List[_Slot]]] = []
        bank_keys: List = [None] * self.n_banks
        tr = active_tracer()
        for b, wave in round_waves:
            bank = self.banks[b]
            sp = (tr.begin("bank.pack_wave", cat="pack", lane=bank._lane)
                  if tr is not None else None)
            skips0 = bank.stats.transpositions_skipped
            saved0 = bank.stats.transpose_s_saved
            paid0 = bank.stats.transpose_s
            st, wave_key, entries = bank._pack_wave(
                queue, wave, lanes, planes_cache,
                n_rows=n_rows, n_cmds=n_cmds, cols=cols, with_tables=False)
            if sp is not None:
                tr.end(sp, slots=len(entries),
                       planes=horizontal_planes(queue, entries))
            self.stats.transpositions_skipped += (
                bank.stats.transpositions_skipped - skips0)
            self.stats.transpose_s_saved += (
                bank.stats.transpose_s_saved - saved0)
            self.stats.transpose_s += bank.stats.transpose_s - paid0
            states[b] = st
            bank_keys[b] = wave_key
            entries_by_bank.append((b, entries))
        return states, bank_keys, entries_by_bank

    def _pack_round(self, queue, round_waves, lanes, planes_cache):
        """Stack one wave per participating bank into the chip arrays.

        Every bank's slab is padded to the round's max (rows, cmds, cols)
        — NOP commands and zero rows are inert — so a single executor
        call replays all banks; idle banks stay all-NOP.  The stacked
        (n_banks, n_subarrays, n_cmds, 13) command tables come from the
        compile-once :data:`repro.core.control_unit.TABLE_CACHE`, keyed
        by the whole round's composition: a repeated round pays zero
        host-side table work."""
        tr = active_tracer()
        t_pack = time.perf_counter()
        sp = (tr.begin("chip.pack_round", cat="pack", banks=len(round_waves))
              if tr is not None else None)
        n_rows, n_cmds, cols = self._round_dims(queue, round_waves, lanes)
        states, bank_keys, entries_by_bank = self._pack_round_states(
            queue, round_waves, lanes, planes_cache, n_rows, n_cmds, cols)
        tables = TABLE_CACHE.get(
            ("chip", self.n_banks, self.n_subarrays, n_cmds,
             tuple(bank_keys)),
            lambda: self._build_round_tables(bank_keys, n_cmds),
            self.executor.sharding)
        if sp is not None:
            # μProgram commands of the packed slots before NOP padding,
            # and the commands the stacked scan steps through
            tr.end(sp,
                   cmds_useful=sum(len(e.uprog.commands)
                                   for _, entries in entries_by_bank
                                   for e in entries),
                   cmds_replayed=self.n_banks * self.n_subarrays * n_cmds)
        pack_s = time.perf_counter() - t_pack
        self.stats.pack_wall_s += pack_s
        for b, _ in round_waves:
            self.banks[b].stats.pack_wall_s += pack_s / len(round_waves)
        sp = (tr.begin("chip.submit", cat="submit", banks=len(round_waves))
              if tr is not None else None)
        fut = self._submit_round(states, tables, entries_by_bank)
        if sp is not None:
            tr.end(sp)
        return entries_by_bank, fut

    def _submit_round(self, states, tables, entries_by_bank):
        """Submit one stacked chip round.  Fault-free: the state copy to
        the device and the async executor call (the command tables are
        already device-resident, from ``TABLE_CACHE``).  Fault-injected:
        the synchronous detect/retry/heal loop over the chip-tier faulty
        executor; the healed numpy stack drains through
        ``_harvest_round`` exactly like a device future."""
        if self.fault is None:
            tr = active_tracer()
            if tr is None:
                return self.executor.run(self.executor.place(states), tables)
            with tr.span("chip.h2d", cat="fetch", bytes=states.nbytes) as sp:
                on_device = self.executor.place(states)
                devices = _devices(on_device)
                sp.attrs.update(devices=devices,
                                shard_bytes=states.nbytes // devices)
            return self.executor.run(on_device, tables)
        from .fault import faulty_execute
        slabs = [((b,), entries, self.banks[b]._fault_rt)
                 for b, entries in entries_by_bank]
        return faulty_execute(
            self.fault, self._faulty_executor.run, states, tables,
            slabs, self.stats.faults, self.cfg)

    def _blacklist_units(self, units) -> int:
        """Retire persistently-failing subarrays (``units`` are
        ``(bank, sid)`` tuples); returns how many are newly
        blacklisted."""
        new = 0
        for u in units:
            b, sid = int(u[-2]), int(u[-1])
            if sid not in self.banks[b]._blacklist:
                self.banks[b]._blacklist.add(sid)
                new += 1
        return new

    def _build_round_tables(self, bank_keys, n_cmds: int) -> np.ndarray:
        """Materialize one chip round's stacked tables (TABLE_CACHE
        build function — runs once per distinct round composition)."""
        out = np.zeros(
            (self.n_banks, self.n_subarrays, n_cmds, CMD_WIDTH), np.int32)
        for b, key in enumerate(bank_keys):
            if key is None:
                continue
            style, _cmds, slot_ops = key
            out[b] = _build_stacked_tables(
                (style, n_cmds, slot_ops), self.n_subarrays)
        return out

    def _account_round(self, queue, entries_by_bank):
        """Charge one chip round: each bank's wave accounts on the bank
        (latency there = that wave), while the chip charges the round's
        max across banks — banks replay concurrently.  All costs come
        from :func:`repro.core.bank.wave_cost`, the same single source
        the bank-level stats use (the calibration pair must never
        desynchronize).  Returns the round's ``bank_waves`` so the
        channel-level dispatcher can apply the same max rule one tier up
        (:func:`repro.core.timing.channel_round_latency_s`)."""
        st = self.stats
        st.rounds += 1
        bank_waves = []
        for b, entries in entries_by_bank:
            idxs = [e.qi for e in entries]
            fused = len({(queue[i].op, queue[i].n_bits, queue[i].signed_out)
                         for i in idxs}) > 1
            c = self.banks[b]._account_wave(
                [(e.uprog, e.lanes, e.sid) for e in entries], fused=fused)
            st.add_wave(c, fused, concurrent=True)
            st.bank_busy_s[b] += c.latency_s
            tr = active_tracer()
            if tr is not None:
                # per-bank modeled busy time on the bank's own lane (the
                # round charges the max across banks; this shows each
                # bank's term of it)
                ev = tr.event("bank.wave", cat="replay",
                              lane=self.banks[b]._lane, slots=len(entries))
                tr.charge("bank.busy", c.latency_s, span=ev)
            for e in entries:
                st.subarray_programs[b * self.n_subarrays + e.sid] += 1
            bank_waves.append((c.uprogs, c.invocations))
        round_s = chip_round_latency_s(bank_waves, self.cfg)
        st.latency_s += round_s
        tr = active_tracer()
        if tr is not None:
            tr.charge("chip.replay", round_s)
        return bank_waves

    def _harvest_round(self, queue, pending, planes_cache, needed, results,
                       barrier: bool):
        """Materialize one completed chip round: copy its state to the
        host, then unpack it bank slab by bank slab.

        ``barrier`` says whether the device had no later round queued
        while this one was harvested: a stage barrier, or the last
        round of the queue.  Traced, the ``chip.unpack`` span splits the
        harvest into the wait for the device (``chip.harvest.wait``),
        the device-to-host copy (``chip.harvest.fetch``, with its
        ``bytes``) and one ``bank.harvest_out`` per bank slab (``planes``:
        output planes it converts to horizontal, ``keep_vertical`` ones
        left out)."""
        entries_by_bank, fut = pending
        tr = active_tracer()
        if tr is None:
            self._unpack_round(queue, entries_by_bank, np.asarray(fut),
                               planes_cache, needed, results)
            return
        with tr.span("chip.unpack", cat="unpack", barrier=barrier):
            with tr.span("chip.harvest.wait", cat="wait"):
                jax.block_until_ready(fut)
            with tr.span("chip.harvest.fetch", cat="fetch",
                         devices=_devices(fut)) as sp:
                out = np.asarray(fut)
                sp.attrs["bytes"] = out.nbytes
            self._unpack_round(queue, entries_by_bank, out, planes_cache,
                               needed, results)

    def _unpack_round(self, queue, entries_by_bank, out, planes_cache,
                      needed, results):
        """Unpack one round's host copy ``out``, bank slab by bank slab
        (forwarded planes published per bank — chains are bank-local)."""
        tr = active_tracer()
        for b, entries in entries_by_bank:
            bank = self.banks[b]
            skips0 = bank.stats.transpositions_skipped
            saved0 = bank.stats.transpose_s_saved
            paid0 = bank.stats.transpose_s
            if tr is None:
                bank._harvest_out(queue, entries, out[b], planes_cache,
                                  needed, results)
            else:
                planes = sum(sum(e.spec.out_bits) for e in entries
                             if not queue[e.qi].keep_vertical)
                with tr.span("bank.harvest_out", cat="unpack",
                             lane=bank._lane, slots=len(entries),
                             planes=planes):
                    bank._harvest_out(queue, entries, out[b], planes_cache,
                                      needed, results)
            self.stats.transpositions_skipped += (
                bank.stats.transpositions_skipped - skips0)
            self.stats.transpose_s_saved += (
                bank.stats.transpose_s_saved - saved0)
            self.stats.transpose_s += bank.stats.transpose_s - paid0

    # -- ISA front-end -----------------------------------------------------
    def bbop(self, name: str, *operands, n_bits: int,
             signed_out: bool = False):
        """One bbop whose lanes span the whole chip: elements split into
        contiguous chunks, one per (bank, subarray) slot, and drain in
        (ideally) one chip round."""
        arrs = [np.asarray(o) for o in operands]
        n = arrs[0].shape[-1]
        if n == 0:
            return self.dispatch(
                [BbopInstr(name, tuple(arrs), n_bits,
                           signed_out=signed_out)])[0]
        slots = self.n_banks * self.n_subarrays
        per = max(1, -(-n // slots))
        queue = [
            BbopInstr(name, tuple(a[..., s: s + per] for a in arrs), n_bits,
                      signed_out=signed_out)
            for s in range(0, n, per)
        ]
        results = self.dispatch(queue)
        if isinstance(results[0], tuple):
            return tuple(np.concatenate([r[i] for r in results], axis=-1)
                         for i in range(len(results[0])))
        return np.concatenate(results, axis=-1)

    def reset_stats(self):
        self.stats = ChipStats(n_subarrays=self.n_banks * self.n_subarrays,
                               n_banks=self.n_banks)
        for bank in self.banks:
            bank.reset_stats()
