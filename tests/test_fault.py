"""Fault-injected execution: detection, bounded retry, degradation.

Covers the fault layer (:mod:`repro.core.fault`) end to end across the
ladder — statistical properties of the injector, bit-exact recovery at
every tier, blacklist/repack degradation, the zero-cost-when-disabled
guarantee, and the serve-layer host fallback — plus the input
validation and TableCache behaviours that ride along.
"""

import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core.bank import Bank, BbopInstr, Ref, flatten_result
from repro.core.fault import (FaultExhaustedError, FaultModel, FaultStats,
                              dereplicate_results, replicate_queue)
from repro.core.ops_library import get_op

U = np.uint64


def _queue(lanes=100, seed=0):
    rng = np.random.default_rng(seed)
    a = rng.integers(0, 256, lanes).astype(U)
    b = rng.integers(0, 256, lanes).astype(U)
    return [
        BbopInstr("addition", (a, b), 8),
        BbopInstr("multiplication", (Ref(0), b), 8),
        BbopInstr("greater", (a, b), 8),
    ]


def _exact(xs, ys):
    return all(np.array_equal(np.asarray(p), np.asarray(q))
               for x, y in zip(xs, ys)
               for p, q in zip(flatten_result(x), flatten_result(y)))


@pytest.fixture(scope="module")
def clean():
    return Bank(n_subarrays=4).dispatch(_queue())


# ---------------------------------------------------------------------------
# fault model construction
# ---------------------------------------------------------------------------

def test_model_validation():
    with pytest.raises(ValueError):
        FaultModel(p_flip=1.5)
    with pytest.raises(ValueError):
        FaultModel(spare_lanes=-1)
    with pytest.raises(ValueError):
        FaultModel(max_retries=-1)


def test_flip_probability_derives_from_reliability():
    from repro.core.reliability import tra_failure_breakdown

    m = FaultModel(sigma=0.15, tech_node="17nm", p_trials=50_000)
    assert m.flip_probability() == pytest.approx(
        tra_failure_breakdown(0.15, n_trials=50_000)["overall"])
    # explicit override wins over the derived value
    assert FaultModel(p_flip=1e-3).flip_probability() == 1e-3


def test_replicate_dereplicate_roundtrip():
    q = _queue(lanes=40)
    rep = replicate_queue(q, 3)
    for ins, orig in zip(rep, q):
        for o, oo in zip(ins.operands, orig.operands):
            if isinstance(oo, Ref):
                assert o is oo
            else:
                # strided layout: replica j of lane l at column j*L + l
                arr = np.asarray(o)
                assert arr.shape[-1] == 3 * np.asarray(oo).shape[-1]
                assert np.array_equal(arr.reshape(3, -1)[1],
                                      np.asarray(oo))
    back = dereplicate_results(
        [np.tile(np.asarray(o), 3) for ins in q
         for o in [ins.operands[1]]], 3)
    for got, ins in zip(back, q):
        assert np.array_equal(got, np.asarray(ins.operands[1]))


# ---------------------------------------------------------------------------
# statistical property: injected flips within binomial confidence bounds
# ---------------------------------------------------------------------------

def _injected_single_run(p, seed, lanes=512):
    """stats.injected for exactly ONE interpreter run (no retries)."""
    model = FaultModel(p_flip=p, spare_lanes=1, seed=seed,
                       max_retries=0, max_redispatches=0)
    bank = Bank(n_subarrays=2, fault=model)
    try:
        bank.dispatch([BbopInstr("multiplication",
                                 (np.arange(lanes, dtype=U) % U(256),
                                  np.arange(lanes, dtype=U) % U(256)),
                                 8)])
    except FaultExhaustedError:
        pass                     # single-attempt runs may not converge
    return bank.stats.faults.injected


def test_flip_rate_within_confidence_bounds():
    # calibrate the per-run Bernoulli draw count with p = 0.5: the
    # injector draws a fixed grid per activation, so injected ≈ n/2
    n_draws = 2 * _injected_single_run(0.5, seed=0)
    assert n_draws > 10_000
    p = 1e-3
    pooled, runs = 0, 8
    for seed in range(runs):
        pooled += _injected_single_run(p, seed=seed)
    mean = runs * n_draws * p
    sd = np.sqrt(runs * n_draws * p * (1 - p))
    assert abs(pooled - mean) < 6 * sd + 10, (pooled, mean, sd)


# ---------------------------------------------------------------------------
# bit-exact detection / retry / remap at every tier
# ---------------------------------------------------------------------------

def test_bank_flips_detected_and_bit_exact(clean):
    bank = Bank(n_subarrays=4,
                fault=FaultModel(p_flip=1e-4, spare_lanes=1, seed=1))
    out = bank.dispatch(_queue())
    assert _exact(out, clean)
    fs = bank.stats.faults
    assert fs.injected > 0 and fs.detected > 0 and fs.retries > 0
    assert fs.overhead_s > 0
    assert bank.stats.total_latency_s > bank.stats.latency_s


def test_bank_checksum_fallback_no_spares(clean):
    # spare_lanes=0: temporal double-run checksum still detects flips
    bank = Bank(n_subarrays=4,
                fault=FaultModel(p_flip=1e-4, spare_lanes=0, seed=2))
    out = bank.dispatch(_queue())
    assert _exact(out, clean)
    assert bank.stats.faults.detected > 0


def test_chip_tier_bit_exact():
    from repro.core.chip import SimdramChip

    q = _queue(lanes=300)
    ref = SimdramChip(n_banks=4, n_subarrays=4).dispatch(_queue(lanes=300))
    chip = SimdramChip(n_banks=4, n_subarrays=4,
                       fault=FaultModel(p_flip=1e-4, spare_lanes=1,
                                        seed=5))
    assert _exact(chip.dispatch(q), ref)
    assert chip.stats.faults.injected > 0


def test_channel_tier_bit_exact():
    from repro.core.channel import SimdramChannel

    q = _queue(lanes=300)
    ref = SimdramChannel(n_chips=2, n_banks=2, n_subarrays=4).dispatch(
        _queue(lanes=300))
    ch = SimdramChannel(n_chips=2, n_banks=2, n_subarrays=4,
                        fault=FaultModel(p_flip=1e-4, spare_lanes=1,
                                         seed=9))
    assert _exact(ch.dispatch(q), ref)
    assert ch.stats.faults.injected > 0


# ---------------------------------------------------------------------------
# stuck-at columns and dead subarrays: blacklist + repack
# ---------------------------------------------------------------------------

def _small_queue(seed=3, lanes=64):
    rng = np.random.default_rng(seed)
    a = rng.integers(0, 256, lanes).astype(U)
    b = rng.integers(0, 256, lanes).astype(U)
    return [BbopInstr("addition", (a, b), 8), BbopInstr("min", (a, b), 8)]


def test_dead_subarrays_blacklisted_and_remapped():
    ref = Bank(n_subarrays=4).dispatch(_small_queue())
    bank = Bank(n_subarrays=4,
                fault=FaultModel(p_flip=0.0, dead_unit_rate=0.4,
                                 spare_lanes=1, seed=11))
    assert bank._fault_rt.dead.any()     # seed picked to draw dead units
    out = bank.dispatch(_small_queue())
    assert _exact(out, ref)
    fs = bank.stats.faults
    assert fs.redispatches > 0 and fs.remapped > 0
    assert bank._blacklist            # dead subarrays now avoided
    # subsequent dispatches route around the blacklist without retrying
    fs2 = FaultStats()
    bank.stats.faults = fs2
    assert _exact(bank.dispatch(_small_queue()), ref)
    assert fs2.redispatches == 0


def test_stuck_column_clusters_survive_strided_replicas():
    ref = Bank(n_subarrays=4).dispatch(_small_queue())
    bank = Bank(n_subarrays=4,
                fault=FaultModel(p_flip=0.0, stuck_lane_rate=0.02,
                                 spare_lanes=2, seed=13))
    out = bank.dispatch(_small_queue())
    assert _exact(out, ref)
    fs = bank.stats.faults
    assert fs.detected > 0 and fs.corrected > 0


def test_stuck_masks_repeat_past_the_physical_row():
    """A state wider than one physical row (65,536 columns, e.g. a
    spare-replicated 65,536-lane operand) holds re-invocations on the
    same columns: its masks repeat the row's defect pattern."""
    from repro.core.fault import FaultRuntime

    rt = FaultRuntime(FaultModel(stuck_lane_rate=0.05, seed=3), (0,), 2)
    row0, row1 = rt.stuck_masks(2048)
    assert row0.any() and row1.any()
    s0, s1 = rt.stuck_masks(5000)
    assert s0.shape == s1.shape == (2, 5000)
    for got, row in ((s0, row0), (s1, row1)):
        np.testing.assert_array_equal(got[:, :2048], row)
        np.testing.assert_array_equal(got[:, 2048:4096], row)
        np.testing.assert_array_equal(got[:, 4096:], row[:, :904])


@pytest.mark.parametrize("lanes", [40, 32_768, 65_535, 65_536, 98_304,
                                   131_072])
@pytest.mark.parametrize("r", [2, 3])
def test_replicas_of_a_lane_never_share_a_physical_column(lanes, r):
    """Every copy of a lane sits on its own physical column modulo the
    65,536-column row, stuck_cluster (4) or more apart once the copies
    wrap past the row, and the layout round-trips."""
    from repro.core.fault import (_PHYS_COLUMNS, _gather_replicas,
                                  _spread_replicas)

    vals = np.arange(lanes, dtype=U)
    rep = _spread_replicas(vals, r)
    assert rep.shape == (r * lanes,)
    assert np.array_equal(rep[:lanes], vals)
    col = np.empty((r, lanes), np.int64)
    for j in range(r):
        col[j, rep[j * lanes:(j + 1) * lanes].astype(np.int64)] = (
            j * lanes + np.arange(lanes))
    phys = col % _PHYS_COLUMNS
    margin = 4 if r * lanes > _PHYS_COLUMNS else 1
    for j in range(r):
        for k in range(j):
            d = (phys[j] - phys[k]) % _PHYS_COLUMNS
            assert np.minimum(d, _PHYS_COLUMNS - d).min() >= margin
    grid = _gather_replicas(rep, r)
    assert np.array_equal(grid, np.repeat(vals[:, None], r, axis=1))


@pytest.mark.parametrize("spare_lanes,n_subarrays,rate,seed,outcome", [
    (1, 4, 3e-5, 3, "remapped"),     # a clean subarray is left to move to
    (1, 2, 0.002, 13, "exhausted"),  # every subarray defective
    (2, 2, 0.002, 13, "outvoted"),
])
def test_stuck_columns_caught_at_row_width(spare_lanes, n_subarrays, rate,
                                           seed, outcome):
    """At 65,536 lanes every copy of a lane wraps onto the same physical
    row: stuck columns must still split the vote, never return a wrong
    answer.  One spare detects and moves the work to a clean subarray
    (or, with none left, gives up); two spares out-vote the stuck copy."""
    q = _small_queue(lanes=65_536)
    ref = Bank(n_subarrays=n_subarrays).dispatch(_small_queue(lanes=65_536))
    bank = Bank(n_subarrays=n_subarrays,
                fault=FaultModel(p_flip=0.0, stuck_lane_rate=rate,
                                 spare_lanes=spare_lanes, seed=seed,
                                 max_redispatches=1))
    if outcome == "exhausted":
        with pytest.raises(FaultExhaustedError):
            bank.dispatch(q)
    else:
        assert _exact(bank.dispatch(q), ref)
    fs = bank.stats.faults
    assert fs.detected > 0
    if outcome == "remapped":
        assert fs.redispatches > 0 and fs.host_fallbacks == 0
    if outcome == "outvoted":
        assert fs.corrected > 0


def test_exhaustion_raises():
    bank = Bank(n_subarrays=2,
                fault=FaultModel(p_flip=0.0, dead_unit_rate=1.0,
                                 spare_lanes=1, seed=1,
                                 max_redispatches=1))
    with pytest.raises(FaultExhaustedError):
        bank.dispatch(_small_queue())


# ---------------------------------------------------------------------------
# disabled model: strictly zero cost
# ---------------------------------------------------------------------------

def test_disabled_model_is_free():
    from repro.core.control_unit import trace_counts

    q = _small_queue()
    plain = Bank(n_subarrays=2)
    r_plain = plain.dispatch(_small_queue())
    t0 = dict(trace_counts())
    off = Bank(n_subarrays=2, fault=FaultModel(enabled=False))
    assert off.fault is None
    r_off = off.dispatch(q)
    assert dict(trace_counts()) == t0    # no retraces
    assert _exact(r_off, r_plain)
    assert off.stats.faults.overhead_s == 0.0
    assert not off.stats.faults.any
    assert off.stats.latency_s == plain.stats.latency_s
    assert off.stats.total_latency_s == plain.stats.total_latency_s


def test_fault_requires_interp_fused():
    with pytest.raises(ValueError):
        Bank(engine="bitplane", fault=FaultModel())
    with pytest.raises(ValueError):
        Bank(fuse=False, fault=FaultModel())


# ---------------------------------------------------------------------------
# serve-layer host fallback on exhaustion
# ---------------------------------------------------------------------------

def test_serve_host_fallback():
    from repro.core.chip import SimdramChip
    from repro.train.serve import PumServeOffload

    rng = np.random.default_rng(0)
    logits = rng.normal(size=(2, 48)).astype(np.float32)
    chip = SimdramChip(n_banks=2, n_subarrays=2,
                       fault=FaultModel(p_flip=0.0, dead_unit_rate=1.0,
                                        spare_lanes=1, seed=1,
                                        max_redispatches=1))
    off = PumServeOffload(chip=chip)
    out = off(logits)
    assert off.host_fallbacks == 1
    assert chip.stats.faults.host_fallbacks == 1
    assert np.array_equal(out, off.reference(logits))


# ---------------------------------------------------------------------------
# property: retry either converges bit-exactly or raises — never silent
# ---------------------------------------------------------------------------

@given(st.integers(0, 10_000), st.sampled_from([1e-4, 3e-4]),
       st.integers(1, 2))
@settings(max_examples=10, deadline=None)
def test_retry_converges_or_raises(seed, p, spares):
    q = _small_queue(seed=4, lanes=32)
    ref = Bank(n_subarrays=2).dispatch(_small_queue(seed=4, lanes=32))
    bank = Bank(n_subarrays=2,
                fault=FaultModel(p_flip=p, spare_lanes=spares, seed=seed))
    try:
        out = bank.dispatch(q)
    except FaultExhaustedError:
        return                       # bounded failure is a valid outcome
    assert _exact(out, ref)


# ---------------------------------------------------------------------------
# input validation (device + engines)
# ---------------------------------------------------------------------------

def test_device_rejects_empty_queue():
    from repro.core.isa import SimdramDevice

    with pytest.raises(ValueError, match="empty queue"):
        SimdramDevice().dispatch([])


def test_unknown_op_rejected():
    with pytest.raises(ValueError, match="unknown op"):
        Bank().dispatch([BbopInstr("add", (np.zeros(4, U),), 8)])


def test_operand_count_rejected():
    with pytest.raises(ValueError, match="operands"):
        Bank().dispatch([BbopInstr("addition", (np.zeros(4, U),), 8)])


def test_lane_mismatch_rejected():
    with pytest.raises(ValueError, match="lane count"):
        Bank().dispatch([BbopInstr(
            "addition", (np.zeros(4, U), np.zeros(8, U)), 8)])


def test_dangling_ref_rejected():
    with pytest.raises(ValueError, match="Ref producer"):
        Bank().dispatch([BbopInstr(
            "addition", (Ref(0), np.zeros(4, U)), 8)])
    with pytest.raises(ValueError, match="out of range"):
        Bank().dispatch([
            BbopInstr("addition", (np.zeros(4, U), np.zeros(4, U)), 8),
            BbopInstr("addition", (Ref(0, out=3), np.zeros(4, U)), 8)])


# ---------------------------------------------------------------------------
# TableCache: byte-budget eviction, counters, key safety
# ---------------------------------------------------------------------------

def test_table_cache_eviction_under_byte_budget():
    from repro.core.control_unit import TableCache

    tc = TableCache(max_bytes=3 * 1024)
    mk = lambda fill: (lambda: np.full((16, 16), fill, np.int32))  # 1 KiB
    for k in range(5):
        tc.get(("key", k), mk(k))
    s = tc.stats()
    assert s["evictions"] == 2 and s["entries"] == 3
    assert s["bytes"] <= 3 * 1024
    # the survivors are the most recently used keys
    assert np.asarray(tc.get(("key", 4), mk(-1)))[0, 0] == 4
    assert tc.stats()["hits"] == 1
    # evicted key rebuilds (miss), not a stale hit
    assert np.asarray(tc.get(("key", 0), mk(-1)))[0, 0] == -1


def test_table_cache_hit_miss_counters():
    from repro.core.control_unit import TableCache

    tc = TableCache()
    build_calls = []
    mk = lambda: (build_calls.append(1),
                  np.zeros((4, 13), np.int32))[1]
    a = tc.get(("composition", 8, "mig"), mk)
    b = tc.get(("composition", 8, "mig"), mk)
    assert b is a                         # device array reused, not rebuilt
    assert len(build_calls) == 1
    assert tc.stats() == {"entries": 1, "bytes": a.nbytes, "hits": 1,
                          "misses": 1, "evictions": 0}
    tc.clear()
    assert tc.stats() == {"entries": 0, "bytes": 0, "hits": 0,
                          "misses": 0, "evictions": 0}


def test_table_cache_key_collision_safety():
    from repro.core.control_unit import TableCache

    tc = TableCache()
    # nearby compositions must not alias: (op,width) pairs that would
    # collide under naive string keys stay distinct as tuples
    k1 = (("addition", 16), ("min", 8))
    k2 = (("addition", 8), ("min", 16))
    a = tc.get(k1, lambda: np.full((2, 2), 1, np.int32))
    b = tc.get(k2, lambda: np.full((2, 2), 2, np.int32))
    assert np.asarray(a)[0, 0] == 1 and np.asarray(b)[0, 0] == 2
    assert tc.stats()["misses"] == 2 and tc.stats()["hits"] == 0
    # and the single-entry floor: one oversized entry is kept even past
    # the budget (evicting it would thrash every dispatch)
    tc2 = TableCache(max_bytes=8)
    big = tc2.get("big", lambda: np.zeros((64, 64), np.int32))
    assert tc2.stats()["entries"] == 1
    assert tc2.get("big", lambda: None) is big
