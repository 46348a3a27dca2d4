"""SIMDRAM Step 3: the control unit that executes μPrograms.

The paper places a small control unit in the memory controller that replays
a stored command sequence ("μProgram memory") whenever the CPU issues a
``bbop`` instruction.  The crucial property: the *same hardware* executes
*any* μProgram — programs are data, not logic.

We reproduce that property in JAX: :func:`encode_uprogram` turns a
μProgram into a dense ``(n_cmds, 13)`` int32 command table, and
:func:`make_interpreter` builds ONE jitted ``lax.scan`` interpreter whose
compiled XLA executable is reused for every operation of the same table
shape — swapping the command table (an input array) never triggers
recompilation.  This is the JAX-native analogue of "add a new operation
without hardware changes".

Command word layout (int32 × 13)::

  [ is_ap,  r0, n0,  r1, n1,  r2, n2,  w0, nw0,  w1, nw1,  w2, nw2 ]

  AAP src→dst :  is_ap=0, (r0,n0)=src port, writes w0..w2 = dst (repeated)
  AP  triple  :  is_ap=1, reads = writes = the triple's three ports

Port semantics match :class:`repro.core.subarray.Subarray` exactly: a
``neg`` port reads/writes the complement (dual-contact cell).
"""

from __future__ import annotations

import functools
import time
from typing import Dict, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .uprogram import C1, TRIPLES, Command, UProgram

CMD_WIDTH = 13
_FULL = np.uint32(0xFFFFFFFF)


# ---------------------------------------------------------------------------
# state layout helpers (shared by the isa "interp" backend and the bank
# engine — one definition of operand loading / output readout)
# ---------------------------------------------------------------------------

def load_state(
    uprog: UProgram, operands: Sequence[np.ndarray], n_columns: int,
    n_rows: int | None = None, out: np.ndarray | None = None,
) -> np.ndarray:
    """(n_rows, n_words) uint32 subarray state: C1 pinned, operand *i*'s
    bits packed vertically into ``uprog.in_rows[i]``.

    An operand entry of ``None`` is skipped — the caller supplies those
    rows already vertical (the bank dispatcher's operand-forwarding path
    writes producer bit-planes straight into the consumer state).
    ``out`` fills an existing zeroed slab in place (the wave packer
    passes its stacked state array's slot) instead of allocating.
    """
    from .subarray import pack_bits

    if out is not None:
        state = out
    else:
        state = np.zeros(
            (n_rows or uprog.n_rows_total, n_columns // 32), dtype=np.uint32)
    state[C1] = np.uint32(0xFFFFFFFF)
    for op_idx, rows in enumerate(uprog.in_rows):
        if operands[op_idx] is None:
            continue
        state[list(rows)] = pack_bits(
            np.asarray(operands[op_idx]), len(rows), n_columns)
    return state


def output_plane_rows(out_bits: Sequence[int], uprog: UProgram):
    """Physical state rows holding each output, LSB-first: one row list
    per declared output width (the rows whose planes ARE the vertical
    result — what the dispatcher forwards without unpacking)."""
    rows, pos = [], 0
    for w in out_bits:
        rows.append([uprog.out_rows[pos + j][0] for j in range(w)])
        pos += w
    return rows


def read_outputs(
    out_bits: Sequence[int], uprog: UProgram, state: np.ndarray,
    lanes: int, signed: bool = False,
):
    """Extract the op's outputs from an executed state: one int64 array
    per declared output width (two's-complement narrowed if ``signed``)."""
    from .subarray import unpack_bits

    outs = []
    for w, rows in zip(out_bits, output_plane_rows(out_bits, uprog)):
        vals = unpack_bits(state[rows], lanes).view(np.int64)
        if signed:
            vals = vals & ((1 << w) - 1)
            vals = np.where(vals >= (1 << (w - 1)), vals - (1 << w), vals)
        outs.append(vals)
    return outs


def encode_uprogram(uprog: UProgram) -> np.ndarray:
    """μProgram -> (n_cmds, 13) int32 command table."""
    rows = []
    for c in uprog.commands:
        if c.kind == "AAP":
            (rs, ns), (rd, nd) = c.src, c.dst
            rows.append([0, rs, ns, rs, ns, rs, ns, rd, nd, rd, nd, rd, nd])
        else:
            t = TRIPLES[c.triple]
            flat: list = [1]
            for r, n in t:
                flat += [r, int(n)]
            for r, n in t:
                flat += [r, int(n)]
            rows.append(flat)
    return np.asarray(rows, dtype=np.int32)


def _step(state: jnp.ndarray, cmd: jnp.ndarray) -> Tuple[jnp.ndarray, None]:
    """Execute one command word on the (n_rows, n_words) uint32 state."""
    is_ap = cmd[0].astype(jnp.uint32)

    def read(r, n):
        v = state[r]
        return v ^ (n.astype(jnp.uint32) * jnp.uint32(0xFFFFFFFF))

    v0 = read(cmd[1], cmd[2])
    v1 = read(cmd[3], cmd[4])
    v2 = read(cmd[5], cmd[6])
    maj = (v0 & v1) | (v0 & v2) | (v1 & v2)
    val = jnp.where(is_ap.astype(bool), maj, v0)

    def write(st, r, n):
        out = val ^ (n.astype(jnp.uint32) * jnp.uint32(0xFFFFFFFF))
        return st.at[r].set(out)

    state = write(state, cmd[7], cmd[8])
    state = write(state, cmd[9], cmd[10])
    state = write(state, cmd[11], cmd[12])
    return state, None


@functools.partial(jax.jit, donate_argnums=0)
def run_command_table(state: jnp.ndarray, table: jnp.ndarray) -> jnp.ndarray:
    """The control unit: scan the command table over the subarray state.

    jit signature depends only on shapes — any μProgram with the same
    command count reuses the compiled executable; different counts compile
    one interpreter each (bounded by the op library size, like the paper's
    μProgram memory).
    """
    state, _ = jax.lax.scan(_step, state, table)
    return state


def make_interpreter():
    """Return a fresh (non-donating) interpreter for repeated use on the
    same buffers in tests."""

    @jax.jit
    def run(state, table):
        state, _ = jax.lax.scan(_step, state, table)
        return state

    return run


# ---------------------------------------------------------------------------
# bank-level batched execution (N subarrays, one compiled interpreter)
# ---------------------------------------------------------------------------
#
# A command row of all zeros decodes to AAP(T0 -> T0): read row 0 through
# its d-port and write the same value back — a true NOP.  Padding every
# encoded table to a bucketed command count therefore lets μPrograms of
# *different* lengths share one (n_cmds, 13) table shape, so one compiled
# scan executable serves many ops (the JAX analogue of the paper's fixed
# μProgram-memory slot size).

def pad_command_table(table: np.ndarray, n_cmds: int) -> np.ndarray:
    """Pad an encoded table with NOP rows up to ``n_cmds`` commands."""
    if table.shape[0] > n_cmds:
        raise ValueError(f"table has {table.shape[0]} cmds > bucket {n_cmds}")
    out = np.zeros((n_cmds, CMD_WIDTH), dtype=np.int32)
    out[: table.shape[0]] = table
    return out


def shape_bucket(x: int, base: int) -> int:
    """Harmonized array-dimension bucket: next power of two ≥ ``base``
    (and ≥ x).  Rounding wave dimensions (rows, columns) to shared
    buckets keeps stacked hetero replays from retriggering XLA traces —
    the set of distinct compiled shapes stays O(log max-dim) instead of
    one per wave composition."""
    b = base
    while b < x:
        b *= 2
    return b


def table_bucket(n_cmds: int, min_bucket: int = 16) -> int:
    """Slot size for a μProgram of ``n_cmds`` commands: next power of two
    ≥ ``min_bucket`` (bounds distinct compiled interpreter shapes to
    O(log max-program-length)).  The floor is 16 commands — small
    compacted programs used to pay a min-64 NOP pad that made their
    scans 2-4× longer than the program itself."""
    return shape_bucket(n_cmds, min_bucket)


# ---------------------------------------------------------------------------
# compile-once replay tables: device-resident command-table cache
# ---------------------------------------------------------------------------

class TableCache:
    """Memoizes encoded+padded+stacked command tables as device-resident
    arrays, keyed by the wave's composition — (op, width, style) per
    slot plus the shared command bucket.  A dispatch that replays a
    composition seen before pays ZERO host-side table work: no
    re-encode, no NOP re-pad, no host→device transfer (the paper's
    μProgram memory: programs are written once and replayed forever —
    and like that memory it has finite capacity: a device-byte budget,
    least-recently-replayed compositions evicting past it, so a
    long-running server with drifting queue mixes cannot grow device
    memory without bound; chip-level round entries run to megabytes
    each, which is why the budget is in bytes, not entries).
    """

    def __init__(self, max_bytes: int = 128 * 1024 * 1024):
        from collections import OrderedDict

        self.max_bytes = max_bytes
        self._store: "OrderedDict" = OrderedDict()
        self.bytes = 0
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    def get(self, key, build, sharding=None):
        """Return the cached device array for ``key``, building (and
        device-committing) it on first use via ``build()``.  A miss is
        placed on ``sharding``, the executor's input sharding, so each
        device holds the slabs it replays (``None``: the default
        device); an entry is cached per sharding."""
        from .telemetry import active_tracer
        tr = active_tracer()
        key = (key, sharding)
        t = self._store.get(key)
        if t is None:
            self.misses += 1
            t0 = time.perf_counter() if tr is not None else 0.0
            arr = build()
            t = self._store[key] = jax.device_put(arr, sharding)
            if tr is not None:
                tr.event("table_cache.miss", cat="cache", tier=key[0][0],
                         wall_s=time.perf_counter() - t0,
                         bytes=int(arr.nbytes),
                         devices=len(t.sharding.device_set))
            self.bytes += int(arr.nbytes)
            while self.bytes > self.max_bytes and len(self._store) > 1:
                _, old = self._store.popitem(last=False)
                self.bytes -= int(old.nbytes)
                self.evictions += 1
        else:
            self.hits += 1
            self._store.move_to_end(key)
            if tr is not None:
                tr.event("table_cache.hit", cat="cache", tier=key[0][0])
        return t

    def stats(self) -> Dict[str, int]:
        return {"entries": len(self._store), "bytes": self.bytes,
                "hits": self.hits, "misses": self.misses,
                "evictions": self.evictions}

    def clear(self) -> None:
        self._store.clear()
        self.bytes = 0
        self.hits = 0
        self.misses = 0
        self.evictions = 0


TABLE_CACHE = TableCache()


def trace_counts() -> Dict[str, int]:
    """Compiled-executable counts of the jitted interpreters — the
    retrace regression gate: a second identical dispatch must leave
    every count unchanged (tables are data; only shapes compile)."""
    return {
        "run_command_table": run_command_table._cache_size(),
        "batched": batched_interpreter()._cache_size(),
        "hetero": hetero_batched_interpreter()._cache_size(),
        "chip": chip_batched_interpreter()._cache_size(),
        "channel": channel_batched_interpreter()._cache_size(),
        "rank": rank_batched_interpreter()._cache_size(),
    }


@functools.lru_cache(maxsize=1)
def batched_interpreter():
    """One jitted vmapped interpreter: (n_subarrays, n_rows, n_words)
    states × one shared (n_cmds, 13) command table.

    Every subarray in the bank replays the same μProgram over its own
    rows — exactly the paper's bank-level parallelism, where the memory
    controller broadcasts one command stream to all compute-enabled
    subarrays.  jit caches per shape: same (state, table) shapes — even
    for different ops, thanks to NOP bucketing — reuse one executable.
    Use ``batched_interpreter()._cache_size()`` to observe compilations.
    """

    @jax.jit
    def run(states: jnp.ndarray, table: jnp.ndarray) -> jnp.ndarray:
        def one(state):
            out, _ = jax.lax.scan(_step, state, table)
            return out

        return jax.vmap(one)(states)

    return run


@functools.lru_cache(maxsize=1)
def hetero_batched_interpreter():
    """Fused heterogeneous replay: (n_subarrays, n_rows, n_words) states ×
    (n_subarrays, n_cmds, 13) *per-subarray* command tables.

    Command tables are data, so stacking them adds one more vmapped axis:
    one replay executes a DIFFERENT μProgram on every subarray — the
    PULSAR-style multi-op simultaneous activation that amortizes a single
    controller broadcast across heterogeneous work.  Shorter constituent
    programs are NOP-padded to the wave's shared command bucket (a
    zero command word is AAP(T0→T0), a true no-op), so the executable is
    cached per (state, table) *shape* exactly like the homogeneous path.
    """

    @jax.jit
    def run(states: jnp.ndarray, tables: jnp.ndarray) -> jnp.ndarray:
        def one(state, table):
            out, _ = jax.lax.scan(_step, state, table)
            return out

        return jax.vmap(one)(states, tables)

    return run


# ---------------------------------------------------------------------------
# fault-injected replay (repro.core.fault)
# ---------------------------------------------------------------------------
#
# Same scan interpreter, with the paper's §5 failure modes woven into the
# array program (masks + jax.random only — no per-element Python branching,
# so every vmap/shard_map axis above is preserved):
#
#   - per-activation TRA bit flips: each AP command XORs a Bernoulli(p)
#     bit mask into its MAJ result (the charge-sharing misread the
#     reliability Monte-Carlo prices as ``tra_failure_rate``);
#   - stuck-at columns: ``stuck1``/``stuck0`` word masks force bits on
#     every row the scan writes (and the initial state), modeling
#     manufacturing-defective bitlines;
#   - dead subarrays: a whole unit's output XORs random garbage, modeling
#     row-decoder / sense-amp block failures.
#
# Flip keys ride in the scan carry, so a single seeded key per subarray
# reproduces the whole command stream's fault pattern deterministically.

def faulty_bank_replay(states, tables, keys, stuck0, stuck1, dead, p_flip):
    """Fault-injected :func:`hetero_batched_interpreter` body.

    Args:
        states: (n_subarrays, n_rows, n_words) uint32.
        tables: (n_subarrays, n_cmds, 13) int32.
        keys:   (n_subarrays, 2) uint32 — per-subarray PRNG keys.
        stuck0/stuck1: (n_subarrays, n_words) uint32 — stuck-at-0/1
            column masks (bit set = that column is defective).
        dead:   (n_subarrays,) bool — whole-subarray failures.
        p_flip: scalar per-activation per-bit flip probability.

    Returns:
        ``(out_states, flip_counts)`` — executed states with faults
        applied, and the number of injected AP bit flips per subarray.
    """

    def one(state, table, key, s0, s1, dd):
        k_noise, k_scan = jax.random.split(jnp.asarray(key, jnp.uint32))
        state = (state | s1[None, :]) & ~s0[None, :]
        weights = jnp.uint32(1) << jnp.arange(32, dtype=jnp.uint32)

        def step(carry, cmd):
            st, k, nf = carry
            k, kf = jax.random.split(k)
            is_ap = cmd[0].astype(jnp.uint32)

            def read(r, n):
                v = st[r]
                return v ^ (n.astype(jnp.uint32) * jnp.uint32(0xFFFFFFFF))

            v0 = read(cmd[1], cmd[2])
            v1 = read(cmd[3], cmd[4])
            v2 = read(cmd[5], cmd[6])
            maj = (v0 & v1) | (v0 & v2) | (v1 & v2)
            val = jnp.where(is_ap.astype(bool), maj, v0)
            flips = jax.random.bernoulli(kf, p_flip, (st.shape[1], 32))
            flip = jnp.sum(flips * weights, axis=1,
                           dtype=jnp.uint32) * is_ap
            val = val ^ flip
            nf = nf + jnp.sum(jax.lax.population_count(flip),
                              dtype=jnp.uint32)

            def write(s, r, n):
                out = val ^ (n.astype(jnp.uint32) * jnp.uint32(0xFFFFFFFF))
                out = (out | s1) & ~s0
                return s.at[r].set(out)

            st = write(st, cmd[7], cmd[8])
            st = write(st, cmd[9], cmd[10])
            st = write(st, cmd[11], cmd[12])
            return (st, k, nf), None

        (out, _, nf), _ = jax.lax.scan(
            step, (state, k_scan, jnp.uint32(0)), table)
        garbage = jax.random.bits(k_noise, out.shape, jnp.uint32)
        out = jnp.where(dd, out ^ garbage, out)
        return out, nf

    return jax.vmap(one)(states, tables, keys, stuck0, stuck1, dead)


@functools.lru_cache(maxsize=1)
def faulty_batched_interpreter():
    """Jitted :func:`faulty_bank_replay` — the bank-tier faulty wave
    executor.  ``p_flip`` is a traced scalar, so sweeping σ never
    recompiles."""
    return jax.jit(faulty_bank_replay)


def faulty_chip_replay(states, tables, keys, stuck0, stuck1, dead, p_flip):
    """Fault-injected :func:`chip_replay`: one more vmapped (bank) axis
    over :func:`faulty_bank_replay` — same shard_map story as the
    fault-free path, because faults are just more per-unit arrays."""
    return jax.vmap(
        lambda st, tb, k, a, b, d: faulty_bank_replay(
            st, tb, k, a, b, d, p_flip)
    )(states, tables, keys, stuck0, stuck1, dead)


@functools.lru_cache(maxsize=1)
def faulty_chip_batched_interpreter():
    """Jitted single-device :func:`faulty_chip_replay` (vmap fallback)."""
    return jax.jit(faulty_chip_replay)


def faulty_channel_replay(states, tables, keys, stuck0, stuck1, dead,
                          p_flip):
    """Fault-injected :func:`channel_replay`: one more vmapped (chip)
    axis over :func:`faulty_chip_replay`."""
    return jax.vmap(
        lambda st, tb, k, a, b, d: faulty_chip_replay(
            st, tb, k, a, b, d, p_flip)
    )(states, tables, keys, stuck0, stuck1, dead)


@functools.lru_cache(maxsize=1)
def faulty_channel_batched_interpreter():
    """Jitted single-device :func:`faulty_channel_replay` (vmap
    fallback)."""
    return jax.jit(faulty_channel_replay)


def chip_replay(states: jnp.ndarray, tables: jnp.ndarray) -> jnp.ndarray:
    """Un-jitted chip-level replay body: (n_banks, n_subarrays, n_rows,
    n_words) states × (n_banks, n_subarrays, n_cmds, 13) tables — one
    more vmapped axis over :func:`hetero_batched_interpreter`'s.  The
    bank axis is embarrassingly parallel (banks share nothing), which is
    what lets :mod:`repro.distributed.pum` ``shard_map`` it over the
    ``data`` mesh axis so bank slabs execute on different devices."""

    def one(state, table):
        out, _ = jax.lax.scan(_step, state, table)
        return out

    return jax.vmap(jax.vmap(one))(states, tables)


@functools.lru_cache(maxsize=1)
def chip_batched_interpreter():
    """Jitted single-device :func:`chip_replay` — the vmap-over-banks
    fallback the chip dispatcher uses when the host has one device (or
    the bank count doesn't divide the mesh).  Bit-exact against the
    sharded executor: both run the same scan per (bank, subarray)."""
    return jax.jit(chip_replay)


def channel_replay(states: jnp.ndarray, tables: jnp.ndarray) -> jnp.ndarray:
    """Un-jitted channel-level replay body: (n_chips, n_banks,
    n_subarrays, n_rows, n_words) states × (n_chips, n_banks,
    n_subarrays, n_cmds, 13) tables — one more vmapped axis over
    :func:`chip_replay`'s.  Chips share nothing (each owns its banks'
    states and tables), so the chip axis is embarrassingly parallel
    exactly like the bank axis one level down — which is what lets
    :mod:`repro.distributed.pum` ``shard_map`` the stack over a 2-D
    ``("channel", "data")`` mesh: chip slabs split across the
    ``channel`` axis, each chip's bank slabs across ``data``."""

    return jax.vmap(chip_replay)(states, tables)


@functools.lru_cache(maxsize=1)
def channel_batched_interpreter():
    """Jitted single-device :func:`channel_replay` — the vmap-over-chips
    fallback the channel dispatcher uses when no multi-device 2-D mesh
    fits.  Bit-exact against the sharded executor: both run the same
    scan per (chip, bank, subarray)."""
    return jax.jit(channel_replay)


def rank_replay(states: jnp.ndarray, tables: jnp.ndarray) -> jnp.ndarray:
    """Un-jitted rank-level replay body: (n_channels, n_chips, n_banks,
    n_subarrays, n_rows, n_words) states × matching (…, n_cmds, 13)
    tables — one more vmapped axis over :func:`channel_replay`'s.
    Channels on a rank share nothing compute-side (each owns its chips'
    states and tables; only the host link is shared, and that is the
    dispatcher's transfer model, not the replay's concern), so the
    channel axis is embarrassingly parallel exactly like the chip and
    bank axes below it — which is what lets :mod:`repro.distributed.pum`
    ``shard_map`` the stack over a 3-D ``("rank", "channel", "data")``
    mesh: channel slabs across ``rank``, chip slabs across ``channel``,
    bank slabs across ``data``."""

    return jax.vmap(channel_replay)(states, tables)


@functools.lru_cache(maxsize=1)
def rank_batched_interpreter():
    """Jitted single-device :func:`rank_replay` — the vmap-over-channels
    fallback the rank dispatcher uses when no multi-device 3-D mesh
    fits.  Bit-exact against the sharded executor: both run the same
    scan per (channel, chip, bank, subarray)."""
    return jax.jit(rank_replay)
