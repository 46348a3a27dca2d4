"""Chip-level partitioned execution vs the sequential per-bank baseline.

Proves the PR-3 tentpole claims:
  - ``SimdramChip.dispatch`` (stacked multi-bank replay, one chip round
    per wave front) is bit-exact against sequential per-bank
    ``Bank.dispatch`` across all 16 ops in both MIG and AIG styles,
    property-tested over random queues/bank geometries;
  - the bin-packing scheduler keeps Ref chains bank-local, balances
    equal loads perfectly, and the chip's modeled latency charges
    concurrent banks (max per round, not the per-bank sum);
  - ``ChipStats`` extends ``BankStats`` with per-bank utilization,
    cross-bank imbalance, and the modeled-vs-measured latency pair;
  - the ``shard_map`` executor (bank slabs on the ``data`` mesh axis)
    is bit-exact against the single-device vmap fallback — exercised
    in-process when the host exposes ≥2 devices (the CI chip step forces
    4 via ``XLA_FLAGS=--xla_force_host_platform_device_count=4``) and
    via a forced-device subprocess otherwise (slow marker);
  - edge cases: empty queue and all-zero-lane queues return cleanly
    with zeroed stats (no empty wave plan), chip-wide ``bbop`` spans
    all banks, ``SimdramDevice(backend="chip")`` routes through it.
"""

import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st
from repro.core.bank import (Bank, BbopInstr, Ref, VerticalOperand,
                             flatten_result, plan_queue)
from repro.core.chip import (ChipStats, SimdramChip, partition_queue,
                             sequential_dispatch)
from repro.core.isa import SimdramDevice, compile_op
from repro.core.ops_library import ALL_OPS, get_op
from repro.core.timing import DramConfig, uprogram_latency_s

LANES = 64
SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _rand_instr(rng, op, n_bits, lanes=LANES, **kw):
    spec = get_op(op, n_bits)
    ops = tuple(rng.integers(0, 1 << w, lanes).astype(np.uint64)
                for w in spec.operand_bits)
    return BbopInstr(op, ops, n_bits, **kw)


def _assert_same(chip_results, ref_results):
    for i, (a, b) in enumerate(zip(chip_results, ref_results)):
        fa, fb = flatten_result(a), flatten_result(b)
        assert len(fa) == len(fb)
        for x, y in zip(fa, fb):
            np.testing.assert_array_equal(x, y, err_msg=f"instr {i}")


def _both(queue, n_banks=4, n_subarrays=2, style="mig", **chip_kw):
    """Chip dispatch vs sequential per-bank dispatch, bit-exact."""
    chip = SimdramChip(n_banks=n_banks, n_subarrays=n_subarrays,
                       style=style, **chip_kw)
    rc = chip.dispatch(queue)
    rs, banks = sequential_dispatch(queue, n_banks=n_banks,
                                    n_subarrays=n_subarrays, style=style)
    _assert_same(rc, rs)
    return chip, banks, rc


# --- bit-exactness --------------------------------------------------------

@pytest.mark.parametrize("style", ["mig", "aig"])
def test_chip_matches_sequential_all_ops(style):
    """All 16 ops in one mixed queue: chip == sequential per-bank, both
    styles (the PR acceptance criterion's test-side gate)."""
    rng = np.random.default_rng({"mig": 0, "aig": 1}[style])
    queue = [_rand_instr(rng, op, 8, lanes=32) for op in ALL_OPS]
    chip, banks, _ = _both(queue, style=style)
    assert chip.stats.bbops == len(queue)
    assert chip.stats.elements == 32 * len(queue)
    # every instruction landed on some bank
    assert chip.stats.bank_programs.sum() == len(queue)


@given(st.integers(2, 6), st.integers(1, 3), st.integers(1, 3),
       st.integers(0, 10_000))
@settings(max_examples=10, deadline=None)
def test_chip_property_random_queues(n_bits, n_banks, n_subarrays, seed):
    """Random op mixes / widths / lane counts / geometries: chip ==
    sequential per-bank == grouped bank."""
    rng = np.random.default_rng(seed)
    ops = ("addition", "subtraction", "min", "max", "greater", "relu")
    queue = []
    for _ in range(int(rng.integers(1, 9))):
        op = ops[int(rng.integers(0, len(ops)))]
        lanes = int(rng.integers(1, 70))
        signed = bool(rng.integers(0, 2)) and op != "greater"
        queue.append(_rand_instr(rng, op, n_bits, lanes=lanes,
                                 signed_out=signed))
    _, _, rc = _both(queue, n_banks=n_banks, n_subarrays=n_subarrays)
    grouped = Bank(n_subarrays=n_subarrays, fuse=False)
    _assert_same(rc, grouped.dispatch(queue))


def test_chip_chain_with_vertical_operands():
    """Ref chains + user VerticalOperand + keep_vertical through the
    chip: forwarded hops are counted in ChipStats and results match the
    grouped baseline."""
    rng = np.random.default_rng(2)
    x, y = (rng.integers(0, 256, LANES).astype(np.uint64) for _ in range(2))
    z = rng.integers(0, 1 << 16, LANES).astype(np.uint64)
    vo = VerticalOperand.from_values(x, 8)
    queue = [
        BbopInstr("multiplication", (x, y), 8),
        BbopInstr("addition", (Ref(0), z), 16),
        BbopInstr("relu", (Ref(1),), 16, keep_vertical=True),
        BbopInstr("addition", (vo, y), 8),
    ]
    chip, _, rc = _both(queue)
    want = (x * y + z) & 0xFFFF
    np.testing.assert_array_equal(
        rc[2].to_values() & 0xFFFF, np.where(want >= 1 << 15, 0, want))
    # 2 Ref hops + 1 VerticalOperand entry + 1 keep_vertical exit
    assert chip.stats.transpositions_skipped == 4
    assert chip.stats.transpose_s_saved > 0


# --- scheduler ------------------------------------------------------------

def test_ref_chains_stay_bank_local():
    """The partitioner never splits a Ref-connected component across
    banks — forwarded planes cannot cross banks."""
    rng = np.random.default_rng(3)
    queue = []
    for _ in range(6):     # six 3-instruction chains
        base = len(queue)
        queue.append(_rand_instr(rng, "multiplication", 8))
        queue.append(BbopInstr("addition",
                               (Ref(base), queue[base].operands[0]), 8))
        queue.append(BbopInstr("relu", (Ref(base + 1),), 8))
    lanes, _, _ = plan_queue(queue)
    bank_of = partition_queue(queue, list(range(len(queue))), lanes, 4)
    for base in range(0, len(queue), 3):
        assert (bank_of[base] == bank_of[base + 1] == bank_of[base + 2])
    # six equal-cost chains over four banks: two banks get two chains,
    # two get one — never three on one bank while another sits idle
    counts = np.bincount([bank_of[i] for i in range(len(queue))],
                         minlength=4)
    assert counts.max() == 6 and counts.min() == 3
    _both(queue)           # and the whole thing is bit-exact


def test_lpt_balances_equal_components():
    """Eight equal-cost instructions on four banks land two per bank —
    perfectly balanced (imbalance 1.0, equal utilization)."""
    rng = np.random.default_rng(4)
    queue = [_rand_instr(rng, "addition", 8) for _ in range(8)]
    chip, _, _ = _both(queue)
    np.testing.assert_array_equal(chip.stats.bank_programs, [2, 2, 2, 2])
    assert chip.stats.imbalance == pytest.approx(1.0)
    assert np.allclose(chip.stats.utilization, chip.stats.utilization[0])


def test_chip_latency_models_concurrent_banks():
    """N identical instructions on N banks cost ONE program latency —
    banks replay concurrently — while the sequential baseline pays N×."""
    rng = np.random.default_rng(5)
    queue = [_rand_instr(rng, "addition", 8) for _ in range(4)]
    chip = SimdramChip(n_banks=4, n_subarrays=1)
    chip.dispatch(queue)
    _, up = compile_op("addition", 8)
    assert chip.stats.rounds == 1
    assert chip.stats.batches == 4          # one wave per bank
    assert chip.stats.latency_s == pytest.approx(uprogram_latency_s(up))
    _, banks = sequential_dispatch(queue, n_banks=4, n_subarrays=1)
    assert sum(b.stats.latency_s for b in banks) == pytest.approx(
        4 * uprogram_latency_s(up))


def test_chip_stats_extend_bank_stats():
    rng = np.random.default_rng(6)
    chip, _, _ = _both([_rand_instr(rng, "addition", 8),
                        _rand_instr(rng, "greater", 8)])
    assert isinstance(chip.stats, ChipStats)
    d = chip.stats.as_dict()
    # the BankStats surface plus the chip extensions
    for key in ("bbops", "batches", "fused_batches", "latency_s",
                "energy_nj", "pack_wall_s", "wall_s", "n_banks", "rounds",
                "bank_busy_s", "bank_programs", "utilization", "imbalance"):
        assert key in d, key
    assert d["n_banks"] == 4
    assert d["wall_s"] > 0 and d["pack_wall_s"] > 0    # measured side
    assert d["latency_s"] > 0                          # modeled side
    assert chip.stats.throughput_gops > 0
    # per-bank stats accumulated too
    assert sum(b.stats.bbops for b in chip.banks) == 2


# --- edge cases -----------------------------------------------------------

def test_empty_and_zero_lane_chip_queues():
    """Empty queues and all-zero-lane queues return cleanly with zeroed
    stats — no empty wave plan, no device round-trip."""
    chip = SimdramChip(n_banks=2, n_subarrays=2)
    assert chip.dispatch([]) == []
    assert chip.stats.rounds == 0 and chip.stats.bbops == 0
    assert chip.stats.latency_s == 0.0

    e = np.zeros(0, np.uint64)
    queue = [BbopInstr("addition", (e, e), 8),
             BbopInstr("relu", (Ref(0),), 8),
             BbopInstr("division", (e, e), 8),
             BbopInstr("abs", (e,), 8, keep_vertical=True)]
    out = chip.dispatch(queue)
    assert np.asarray(out[0]).shape == (0,)
    assert np.asarray(out[1]).shape == (0,)
    assert all(np.asarray(o).shape == (0,) for o in out[2])
    assert isinstance(out[3], VerticalOperand) and out[3].lanes == 0
    assert chip.stats.rounds == 0 and chip.stats.latency_s == 0.0
    assert chip.stats.bbops == len(queue)
    # Bank.dispatch([]) likewise: clean zeroed stats
    bank = Bank(n_subarrays=2)
    assert bank.dispatch([]) == []
    assert bank.stats.batches == 0 and bank.stats.wall_s == 0.0

    # zero-lane instructions inside a mixed queue still work
    rng = np.random.default_rng(7)
    mixed = [_rand_instr(rng, "addition", 8),
             BbopInstr("addition", (e, e), 8),
             _rand_instr(rng, "greater", 8)]
    chip2, _, rm = _both(mixed, n_banks=2)
    assert np.asarray(rm[1]).shape == (0,)
    assert chip2.stats.bank_programs.sum() == 2


def test_chip_bbop_spans_banks():
    """One wide bbop splits lanes across every (bank, subarray) slot and
    reassembles in order — ideally one chip round."""
    rng = np.random.default_rng(8)
    x = rng.integers(0, 256, 1000)
    y = rng.integers(0, 256, 1000)
    chip = SimdramChip(n_banks=4, n_subarrays=2)
    got = chip.bbop("addition", x, y, n_bits=8)
    want = get_op("addition", 8).oracle(
        x.astype(np.uint64), y.astype(np.uint64))[0]
    np.testing.assert_array_equal(
        got.astype(np.int64) & 0xFF, want.astype(np.int64) & 0xFF)
    assert chip.stats.rounds == 1
    assert chip.stats.bank_programs.sum() == 8


def test_device_chip_backend():
    """SimdramDevice(backend="chip") routes bbops and queue dispatch
    through the chip engine with per-call accounting."""
    dev = SimdramDevice(cfg=DramConfig(n_banks=2, subarrays_per_bank=2),
                        backend="chip")
    rng = np.random.default_rng(9)
    x, y = (rng.integers(0, 256, LANES).astype(np.uint64) for _ in range(2))
    got = dev.bbop("addition", x, y, n_bits=8)
    np.testing.assert_array_equal(
        np.asarray(got) & 0xFF, (x + y) & 0xFF)
    out = dev.dispatch([BbopInstr("addition", (x, y), 8),
                        BbopInstr("relu", (Ref(0),), 8)])
    want = (x + y) & 0xFF
    np.testing.assert_array_equal(
        np.asarray(out[1]) & 0xFF, np.where(want >= 128, 0, want))
    assert dev.chip().n_banks == 2
    assert dev.totals()["calls"] == 3
    assert dev.chip().stats.transpositions_skipped == 1


def test_chip_validation():
    with pytest.raises(ValueError):
        SimdramChip(n_banks=0)
    with pytest.raises(ValueError):
        SimdramChip(n_banks=2, packing="nope")


# --- sharded executor -----------------------------------------------------

def test_vmap_fallback_on_single_device():
    """With one device (the tier-1 default), the executor falls back to
    the vmapped path; requiring shard_map raises."""
    if jax.device_count() > 1:
        pytest.skip("host exposes multiple devices")
    chip = SimdramChip(n_banks=4, n_subarrays=2)
    assert not chip.executor.sharded
    with pytest.raises(ValueError, match="shard_map requested"):
        SimdramChip(n_banks=4, n_subarrays=2, use_shard_map=True)


def test_sharded_executor_multi_device():
    """Real shard_map partitioning (bank slabs on different devices) is
    bit-exact vs the vmap fallback — runs when the host exposes ≥2
    devices (the CI chip step forces 4)."""
    if jax.device_count() < 2:
        pytest.skip("needs >= 2 devices "
                    "(XLA_FLAGS=--xla_force_host_platform_device_count=4)")
    rng = np.random.default_rng(10)
    queue = [_rand_instr(rng, op, w)
             for op in ("addition", "multiplication", "greater", "min")
             for w in (8, 16)]
    base = len(queue)
    queue.append(_rand_instr(rng, "multiplication", 8))
    queue.append(BbopInstr("relu", (Ref(base),), 8, keep_vertical=True))
    sharded = SimdramChip(n_banks=4, n_subarrays=2, use_shard_map=True)
    assert sharded.executor.sharded
    assert sharded.executor.mesh.shape["data"] >= 2
    fallback = SimdramChip(n_banks=4, n_subarrays=2, use_shard_map=False)
    _assert_same(sharded.dispatch(queue), fallback.dispatch(queue))
    _assert_same(sequential_dispatch(queue, 4, 2)[0],
                 fallback.dispatch(queue))


@pytest.mark.slow
def test_sharded_executor_forced_devices_subprocess():
    """Belt-and-braces: force 4 host devices in a subprocess and check
    the shard_map path end to end (covers local single-device runs)."""
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import numpy as np
        from repro.core.bank import BbopInstr, Ref
        from repro.core.chip import SimdramChip, sequential_dispatch
        from repro.core.ops_library import get_op

        rng = np.random.default_rng(0)
        queue = []
        for op in ("addition", "multiplication", "greater", "xor_red"):
            spec = get_op(op, 8)
            ops = tuple(rng.integers(0, 1 << w, 64).astype(np.uint64)
                        for w in spec.operand_bits)
            queue.append(BbopInstr(op, ops, 8))
        queue.append(BbopInstr("relu", (Ref(0),), 8))
        chip = SimdramChip(n_banks=4, n_subarrays=2, use_shard_map=True)
        assert chip.executor.sharded
        rc = chip.dispatch(queue)
        rs, _ = sequential_dispatch(queue, 4, 2)
        for a, b in zip(rc, rs):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        print("SHARDED_CHIP_OK", chip.executor.mesh.shape["data"])
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    out = subprocess.run([sys.executable, "-c", script],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "SHARDED_CHIP_OK 4" in out.stdout


# --- placement of round state and tables -----------------------------------

_SHARDED_Q6 = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import dataclasses
    import numpy as np
    from jax.sharding import NamedSharding
    from repro.apps.tpch import q6_queue
    from repro.core.bank import flatten_result
    from repro.core.chip import SimdramChip
    from repro.core.control_unit import TABLE_CACHE
    from repro.distributed.pum import BANK_SPEC

    def recording(chip):
        seen, plain = [], chip.executor
        def run(states, tables):
            seen.append((states, tables))
            return plain.run(states, tables)
        chip.executor = dataclasses.replace(plain, run=run)
        return seen

    queue, finish = q6_queue(16 * 48, 16, seed=3)
    TABLE_CACHE.clear()
    chip = SimdramChip(n_banks=16, n_subarrays=1, use_shard_map=True)
    mesh = chip.executor.mesh
    assert mesh.shape["data"] == 4
    banks = NamedSharding(mesh, BANK_SPEC)
    seen = recording(chip)
    got = chip.dispatch(queue)
    finish(got)
    misses = TABLE_CACHE.misses
    assert seen and 0 < misses <= len(seen)
    for states, tables in seen:
        for arr in (states, tables):
            assert arr.sharding.is_equivalent_to(banks, arr.ndim)
            assert len(arr.sharding.device_set) == 4
            shards = arr.addressable_shards
            assert sorted(s.index[0].start for s in shards) == [0, 4, 8, 12]
            assert all(s.data.shape[0] == 4 for s in shards)

    twin = SimdramChip(n_banks=16, n_subarrays=1, use_shard_map=False)
    twin_seen = recording(twin)
    want = twin.dispatch(queue)
    for a, b in zip(got, want):
        for x, y in zip(flatten_result(a), flatten_result(b)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    for states, tables in twin_seen:
        assert len(states.sharding.device_set) == 1
        assert len(tables.sharding.device_set) == 1
    # the same round compositions, cached once per sharding
    assert TABLE_CACHE.misses == 2 * misses
    assert TABLE_CACHE.stats()["entries"] == 2 * misses
    chip.dispatch(queue)
    assert TABLE_CACHE.misses == 2 * misses
    print("PLACED_OK", len(seen))
""")


def test_sharded_round_state_and_tables_land_on_the_bank_sharding():
    """On four devices a Q6 queue's every round reaches the executor as
    four bank slabs, one on each device, and so does its cached table
    stack; the answers are bit-exact against the one-device twin, whose
    table entries the cache keeps apart."""
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    out = subprocess.run([sys.executable, "-c", _SHARDED_Q6],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "PLACED_OK" in out.stdout


def test_one_device_round_state_is_a_single_device_array():
    """Without a mesh the executor takes each round whole, on the
    default device, as ``jnp.asarray`` puts it there."""
    import dataclasses

    chip = SimdramChip(n_banks=4, n_subarrays=2, use_shard_map=False)
    seen, plain = [], chip.executor

    def run(states, tables):
        seen.append((states, tables))
        return plain.run(states, tables)

    chip.executor = dataclasses.replace(plain, run=run)
    rng = np.random.default_rng(11)
    queue = [_rand_instr(rng, op, 8) for op in ("addition", "greater")]
    _assert_same(chip.dispatch(queue), sequential_dispatch(queue, 4, 2)[0])
    assert seen
    default = jax.devices()[0]
    for states, tables in seen:
        assert states.sharding.device_set == {default}
        assert tables.sharding.device_set == {default}
    host = np.arange(24, dtype=np.uint32).reshape(2, 3, 4)
    placed = chip.executor.place(host)
    assert placed.sharding == jax.numpy.asarray(host).sharding
    np.testing.assert_array_equal(np.asarray(placed), host)


def test_table_cache_keeps_one_entry_per_sharding():
    from jax.sharding import SingleDeviceSharding

    from repro.core.control_unit import TableCache

    cache, built = TableCache(), []

    def build():
        built.append(1)
        return np.ones((2, 3), np.int32)

    key = ("chip", 2, 1, 3, ())
    one = SingleDeviceSharding(jax.devices()[0])
    a = cache.get(key, build)
    b = cache.get(key, build, one)
    assert cache.get(key, build) is a
    assert cache.get(key, build, one) is b
    assert len(built) == 2 and cache.stats()["entries"] == 2
    assert (cache.misses, cache.hits) == (2, 2)
    assert b.sharding == one
