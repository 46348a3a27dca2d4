"""Ladder-wide telemetry: dual-clock spans, registry, recorder, exporters.

Covers :mod:`repro.core.telemetry` and the ``repro.obs`` facade end to
end — span nesting across bank→chip→channel, bit-for-bit reconciliation
of the modeled clock against the ``Stats`` accumulators, flight-recorder
capture on ``FaultExhaustedError`` and serve host-fallback, the
disabled-tracer-is-free guarantee, the shared ``_FIELD_SPEC``
serialization the three Stats tiers derive ``as_dict()`` from, and the
Chrome-trace / JSONL / stage-summary exporters (validated with the same
schema gate CI runs via ``scripts/check_trace.py``).
"""

import dataclasses
import importlib.util
import json
import os
import pathlib
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro import obs
from repro.core.bank import Bank, BankStats, BbopInstr, Ref, flatten_result
from repro.core.channel import ChannelStats, SimdramChannel
from repro.core.chip import ChipStats, SimdramChip
from repro.core.fault import FaultExhaustedError, FaultModel, FaultStats
from repro.core.telemetry import MetricsRegistry, Tracer, collect_field_spec

U = np.uint64
REPO = pathlib.Path(__file__).resolve().parents[1]


def _queue(lanes=64, seed=0):
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


# ---------------------------------------------------------------------------
# tracer mechanics
# ---------------------------------------------------------------------------

def test_disabled_by_default_and_facade_noops():
    assert obs.active_tracer() is None
    # the facade is safe (and free) without a tracer installed
    with obs.span("anything") as sp:
        assert sp is None
    obs.charge("cat", 1.0)
    assert obs.incident("nope") is None
    assert obs.incidents() == []


def test_span_nesting_charges_and_unwind():
    tr = Tracer()
    root = tr.begin("root", cat="dispatch")
    with tr.span("child", lane="bank0") as child:
        tr.charge("replay", 1.0)
        grand = tr.begin("grand")
        assert grand.lane == "bank0"     # lane inherits from the parent
        tr.charge("replay", 2.0)
        tr.end(grand)
    tr.charge("other", 0.5)
    tr.end(root)

    assert tr.depth == 0
    assert list(tr.roots) == [root]
    assert [s.name for s in root.walk()] == ["root", "child", "grand"]
    assert child.modeled_s == 1.0            # exclusive
    assert child.modeled_total_s == 3.0      # inclusive of grand
    assert root.modeled_total_s == 3.5
    assert tr.modeled_total("replay") == 3.0
    assert tr.modeled_categories() == ("other", "replay")
    assert root.find("grand") == [grand]
    assert all(s.wall_s >= 0.0 for s in root.walk())

    # exception recovery: unwind closes everything an abort left open
    depth0 = tr.depth
    tr.begin("attempt")
    tr.begin("deep")
    assert tr.depth == depth0 + 2
    tr.unwind(depth0, aborted=True)
    assert tr.depth == depth0
    assert tr.roots[-1].name == "attempt"
    assert tr.roots[-1].attrs["aborted"] is True


def test_enabled_scope_restores_previous_tracer():
    assert obs.active_tracer() is None
    with obs.enabled() as tr:
        assert obs.active_tracer() is tr
        with obs.enabled() as inner:
            assert obs.active_tracer() is inner
        assert obs.active_tracer() is tr
    assert obs.active_tracer() is None


def test_flight_recorder_ring_is_bounded():
    tr = Tracer(max_dispatches=3)
    for i in range(5):
        with tr.span(f"d{i}"):
            pass
    assert [r.name for r in tr.roots] == ["d2", "d3", "d4"]
    rec = tr.incident("why", detail=7)
    assert rec.reason == "why" and rec.attrs == {"detail": 7}
    assert [r.name for r in rec.roots] == ["d2", "d3", "d4"]
    assert rec.open_spans == []


# ---------------------------------------------------------------------------
# dual-clock reconciliation against the Stats accumulators (bit-for-bit)
# ---------------------------------------------------------------------------

def test_bank_dual_clock_reconciles_bit_exact():
    ref = Bank(n_subarrays=2).dispatch(_queue())
    with obs.enabled() as tr:
        bank = Bank(n_subarrays=2)
        out = bank.dispatch(_queue())
        st = bank.stats
        assert tr.modeled_total("bank.replay") == st.latency_s
        assert tr.modeled_total("transpose") == st.transpose_s
        assert tr.modeled_total("transpose_saved") == st.transpose_s_saved
        roots = list(tr.roots)
    assert _exact(out, ref)
    assert len(roots) == 1 and roots[0].name == "bank.dispatch"
    assert roots[0].wall_s > 0.0


def test_span_nesting_across_the_ladder():
    with obs.enabled() as tr:
        ch = SimdramChannel(n_chips=2, n_banks=1, n_subarrays=2)
        ch.dispatch(_queue(lanes=128))
        st = ch.stats
        assert tr.modeled_total("channel.replay") == st.latency_s
        assert (tr.modeled_total("channel.transfer.h2d")
                == st.transfer_h2d_s)
        assert (tr.modeled_total("channel.transfer.d2h")
                == st.transfer_d2h_s)
        assert (tr.modeled_total("channel.transfer.overlapped")
                == st.transfer_overlapped_s)
        root = tr.roots[-1]
    assert root.name == "channel.dispatch"
    names = {s.name for s in root.walk()}
    assert {"channel.pack_super_round", "chip.pack_round",
            "bank.pack_wave", "channel.replay",
            "channel.transfer.h2d", "channel.unpack"} <= names
    lanes = {s.lane for s in root.walk()}
    assert "chip0" in lanes and any("/bank" in ln for ln in lanes)


def test_transfer_charges_reconcile_span_by_span():
    """The DMA charge stream is carried on the spans themselves: folding
    every span's ordered ``charges`` list reproduces ``modeled_total``
    AND the Stats accumulators exactly (``==``, not isclose) — at the
    channel tier and at the rank tier (where ``rank.*`` categories own
    the shared host link and ``channel.busy`` carries each member
    channel's replay time)."""
    from repro.core.rank import SimdramRank

    with obs.enabled() as tr:
        ch = SimdramChannel(n_chips=2, n_banks=2, n_subarrays=2)
        ch.dispatch(_queue(lanes=128))
        st = ch.stats
        for cat, want in (("channel.transfer.h2d", st.transfer_h2d_s),
                          ("channel.transfer.d2h", st.transfer_d2h_s),
                          ("channel.transfer.overlapped",
                           st.transfer_overlapped_s)):
            assert tr.modeled_total(cat) == want
            folded = 0.0
            for root in tr.roots:
                for sp in root.walk():
                    for c, s in sp.charges:
                        if c == cat:
                            folded += s
            assert folded == want
        # every transfer span is byte-annotated and burst-aligned
        spans = [s for root in tr.roots for s in root.walk()
                 if s.name.startswith("channel.transfer.")
                 and s.name != "channel.transfer.overlapped"]
        assert spans
        assert all(s.attrs["bytes"] > 0 for s in spans)
        assert sum(s.attrs["bytes"] for s in spans) == st.transfer_bytes

    with obs.enabled() as tr:
        rank = SimdramRank(use_shard_map=False)
        rank.dispatch(_queue(lanes=128))
        st = rank.stats
        assert tr.modeled_total("rank.transfer.h2d") == st.transfer_h2d_s
        assert tr.modeled_total("rank.transfer.d2h") == st.transfer_d2h_s
        assert (tr.modeled_total("rank.transfer.overlapped")
                == st.transfer_overlapped_s)
        assert tr.modeled_total("rank.replay") == st.latency_s
        # member channels charge their busy time but never the link
        assert tr.modeled_total("channel.busy") == sum(
            ch.stats.latency_s for ch in rank.channels)
        assert "channel.transfer.h2d" not in tr.modeled_categories()


def test_disabled_tracer_and_disabled_overlap_add_zero_retraces():
    """Neither knob touches the jitted interpreters: dispatching with
    telemetry off, on, and with ``transfer_overlap=False`` reuses the
    warmed XLA traces — and the overlap knob changes no results and no
    link charges, only the exposed/overlapped split."""
    from dataclasses import replace

    from repro.core.control_unit import trace_counts
    from repro.core.timing import DDR4

    base = SimdramChannel(n_chips=2, n_banks=1, n_subarrays=2)
    r_base = base.dispatch(_queue(seed=5))
    t0 = dict(trace_counts())

    with obs.enabled():
        traced = SimdramChannel(n_chips=2, n_banks=1, n_subarrays=2)
        r_traced = traced.dispatch(_queue(seed=5))
    assert dict(trace_counts()) == t0       # tracer: no retraces

    serial = SimdramChannel(n_chips=2, n_banks=1, n_subarrays=2,
                            cfg=replace(DDR4, transfer_overlap=False))
    r_serial = serial.dispatch(_queue(seed=5))
    assert dict(trace_counts()) == t0       # overlap knob: no retraces

    assert _exact(r_traced, r_base) and _exact(r_serial, r_base)
    for eng in (traced, serial):
        assert eng.stats.transfer_h2d_s == base.stats.transfer_h2d_s
        assert eng.stats.transfer_d2h_s == base.stats.transfer_d2h_s
        assert eng.stats.latency_s == base.stats.latency_s
    assert serial.stats.transfer_overlapped_s == 0.0
    assert serial.stats.exposed_transfer_s == serial.stats.transfer_s


def test_traced_dispatch_changes_nothing():
    plain = Bank(n_subarrays=2)
    r_plain = plain.dispatch(_queue(seed=3))
    with obs.enabled():
        traced = Bank(n_subarrays=2)
        r_traced = traced.dispatch(_queue(seed=3))
    assert _exact(r_traced, r_plain)
    # the modeled cost model is identical with and without the tracer
    assert traced.stats.latency_s == plain.stats.latency_s
    assert traced.stats.transpose_s == plain.stats.transpose_s
    assert traced.stats.energy_nj == plain.stats.energy_nj
    assert obs.active_tracer() is None


# ---------------------------------------------------------------------------
# flight recorder on real incidents
# ---------------------------------------------------------------------------

def test_flight_recorder_captures_fault_exhaustion():
    with obs.enabled() as tr:
        bank = Bank(n_subarrays=2,
                    fault=FaultModel(p_flip=0.0, dead_unit_rate=1.0,
                                     spare_lanes=1, seed=1,
                                     max_redispatches=1))
        with pytest.raises(FaultExhaustedError):
            bank.dispatch(_queue(lanes=32, seed=4))
        recs = [r for r in tr.incidents if r.reason == "fault_exhausted"]
        assert recs, "exhaustion must snapshot the flight recorder"
        assert recs[-1].attrs["cause"] in ("redispatch_budget",
                                           "no_capacity")
        # the aborted dispatch's spans were unwound — the stack is clean
        # and the next dispatch starts a fresh root, not a stale child
        assert tr.depth == 0
        clean = Bank(n_subarrays=2)
        clean.dispatch(_queue(lanes=32, seed=4))
        assert tr.roots[-1].name == "bank.dispatch"


def test_serve_host_fallback_records_incident_and_counter():
    from repro.train.serve import PumServeOffload

    obs.reset()
    rng = np.random.default_rng(0)
    logits = rng.normal(size=(2, 48)).astype(np.float32)
    with obs.enabled() as tr:
        chip = SimdramChip(n_banks=2, n_subarrays=2,
                           fault=FaultModel(p_flip=0.0, dead_unit_rate=1.0,
                                            spare_lanes=1, seed=1,
                                            max_redispatches=1))
        off = PumServeOffload(chip=chip)
        out = off(logits)
        assert off.host_fallbacks == 1
        assert np.array_equal(out, off.reference(logits))
        reasons = [r.reason for r in tr.incidents]
        assert "serve_host_fallback" in reasons
        root = tr.roots[-1]
    assert root.name == "serve.offload"
    assert root.attrs.get("fallback") is True
    assert root.find("serve.host_fallback")
    assert obs.REGISTRY.counter("serve.host_fallbacks").value == 1.0


# ---------------------------------------------------------------------------
# shared field-spec serialization: one definition, three tiers
# ---------------------------------------------------------------------------

def test_field_spec_tiers_are_consistent_supersets():
    # ChipStats and ChannelStats both derive from BankStats, so each
    # emits a consistent superset of the bank tier's keys plus its own
    bank_spec = dict(collect_field_spec(BankStats))
    chip_spec = dict(collect_field_spec(ChipStats))
    chan_spec = dict(collect_field_spec(ChannelStats))
    assert set(bank_spec) <= set(chip_spec)
    assert set(bank_spec) <= set(chan_spec)
    assert {"rounds", "bank_busy_s"} <= set(chip_spec)
    assert {"super_rounds", "transfer_s"} <= set(chan_spec)
    # inherited keys keep their kind — no tier redefines a field's shape
    for key, kind in bank_spec.items():
        assert chip_spec[key] == kind and chan_spec[key] == kind


def test_as_dict_round_trips_through_the_spec():
    q = _queue(lanes=128)
    bank = Bank(n_subarrays=2)
    bank.dispatch(_queue(lanes=128))
    chip = SimdramChip(n_banks=2, n_subarrays=2)
    chip.dispatch(_queue(lanes=128))
    ch = SimdramChannel(n_chips=2, n_banks=1, n_subarrays=2)
    ch.dispatch(q)

    dicts = [bank.stats.as_dict(), chip.stats.as_dict(),
             ch.stats.as_dict()]
    # both aggregate tiers serialize a superset of the bank tier's keys
    # (fault-free, so no tier emits "faults")
    assert set(dicts[0]) <= set(dicts[1])
    assert set(dicts[0]) <= set(dicts[2])
    for d in dicts:
        assert "faults" not in d
        json.dumps(d)        # JSON-serializable end to end
        spec = {k for k, kind in collect_field_spec(type(bank.stats))
                if kind != "stats_if_any"}
        assert spec <= set(d)
        assert d["throughput_total_gops"] <= d["throughput_gops"]
    # a fault-exercised tier emits the full FaultStats block
    fs = FaultStats()
    fs.injected = 3
    fs.overhead_s = 1e-6
    assert set(FaultStats().as_dict()) == set(fs.as_dict())
    assert fs.as_dict()["injected"] == 3


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def test_registry_counters_gauges_histograms():
    reg = MetricsRegistry()
    reg.counter("a.hits").inc()
    reg.counter("a.hits").inc(2)
    reg.gauge("a.level").set(7)
    for v in (1.0, 3.0):
        reg.histogram("b.lat").observe(v)
    snap = reg.snapshot()
    assert snap["a.hits"] == 3.0 and snap["a.level"] == 7.0
    assert snap["b.lat.count"] == 2 and snap["b.lat.mean"] == 2.0
    assert snap["b.lat.min"] == 1.0 and snap["b.lat.max"] == 3.0
    assert set(reg.snapshot("a.")) == {"a.hits", "a.level"}
    reg.reset()
    assert reg.snapshot() == {}


def test_publish_stats_flattens_into_gauges():
    chip = SimdramChip(n_banks=2, n_subarrays=2,
                       fault=FaultModel(p_flip=1e-4, spare_lanes=1, seed=1))
    chip.dispatch(_queue())
    reg = MetricsRegistry()
    flat = obs.publish_stats(chip.stats, "chip.mix", registry=reg)
    snap = reg.snapshot("chip.mix.")
    assert snap == {k: float(v) for k, v in flat.items()}
    assert snap["chip.mix.latency_s"] == chip.stats.latency_s
    # nested FaultStats recurses with a dotted prefix
    assert snap["chip.mix.faults.injected"] == chip.stats.faults.injected
    # list-valued fields publish length and sum
    assert snap["chip.mix.bank_busy_s.len"] == len(chip.stats.bank_busy_s)
    assert snap["chip.mix.bank_busy_s.sum"] == float(
        sum(chip.stats.bank_busy_s))


# ---------------------------------------------------------------------------
# exporters (same schema gate CI runs on TRACE_channel.json)
# ---------------------------------------------------------------------------

def _load_check_trace():
    spec = importlib.util.spec_from_file_location(
        "check_trace", REPO / "scripts" / "check_trace.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_chrome_trace_export_passes_the_ci_schema_gate(tmp_path):
    with obs.enabled() as tr:
        ch = SimdramChannel(n_chips=2, n_banks=1, n_subarrays=2)
        ch.dispatch(_queue(lanes=128))
        trace = obs.write_chrome_trace(str(tmp_path / "trace.json"))
        n_spans = tr.n_spans
    reloaded = json.loads((tmp_path / "trace.json").read_text())
    assert reloaded["traceEvents"] == trace["traceEvents"]
    errors = _load_check_trace().check_trace(reloaded)
    assert errors == []
    x_events = [e for e in trace["traceEvents"] if e["ph"] == "X"]
    assert {e["pid"] for e in x_events} == {1, 2}
    measured = [e for e in x_events if e["pid"] == 1]
    assert len(measured) == n_spans
    # modeled events carry the per-category reconciliation surface
    totals = trace["otherData"]["modeled_totals_s"]
    assert totals["channel.replay"] == ch.stats.latency_s


def test_jsonl_and_stage_summary(tmp_path):
    with obs.enabled() as tr:
        bank = Bank(n_subarrays=2)
        bank.dispatch(_queue())
        path = tmp_path / "spans.jsonl"
        n = obs.write_jsonl(str(path))
        assert n == tr.n_spans > 0
        trace = obs.chrome_trace()
    records = [json.loads(line) for line in path.read_text().splitlines()]
    assert len(records) == n
    roots = [r for r in records if r["parent"] == -1]
    assert [r["name"] for r in roots] == ["bank.dispatch"]
    by_id = {r["id"]: r for r in records}
    assert all(r["parent"] in by_id for r in records if r["parent"] != -1)

    rows = {r["stage"]: r for r in obs.stage_summary(trace)}
    assert rows["bank.dispatch"]["count"] == 1
    assert rows["bank.dispatch"]["wall_us"] > 0.0
    # the root's modeled duration is inclusive — it equals the sum of
    # every category the tracer charged during the dispatch
    assert rows["bank.dispatch"]["modeled_us"] == pytest.approx(
        sum(trace["otherData"]["modeled_totals_s"].values()) * 1e6,
        rel=1e-9)


# ---------------------------------------------------------------------------
# the chip tier's harvest, submit and command counters
# ---------------------------------------------------------------------------

def _chip_device():
    from dataclasses import replace

    from repro.core.isa import SimdramDevice
    from repro.core.timing import DDR4
    cfg = replace(DDR4, n_banks=2, subarrays_per_bank=2,
                  columns_per_subarray=256)
    return SimdramDevice(cfg=cfg, backend="chip")


def _recording(chip):
    """Wrap the chip's executor: record each submitted state, table
    stack and replay output."""
    from dataclasses import replace
    calls = []
    plain = chip.executor

    def run(states, tables):
        out = plain.run(states, tables)
        calls.append((states, tables, out))
        return out

    chip.executor = replace(plain, run=run)
    return calls


def test_annotations_close_with_their_spans_and_stay_out_of_records():
    tr = Tracer()
    root = tr.begin("root")
    tr.begin("left_open")
    child = tr.begin("child")
    assert child.annotation is not None
    tr.end(root)                      # LIFO pop through the open spans
    assert all(s.annotation is None for s in root.walk())
    tr.begin("a")
    tr.begin("b")
    tr.unwind(0)
    assert all(s.annotation is None for s in tr.roots[-1].walk())
    assert "annotation" not in root.to_record()
    assert "annotation" not in repr(root)
    ev = tr.event("leaf", wall_s=1.0)
    assert ev.annotation is None
    twin = tr.begin("x")
    assert twin == dataclasses.replace(twin, annotation=None)
    tr.end(twin)


def test_chip_harvest_splits_into_wait_fetch_and_unpack():
    from repro.core.bank import cached_table

    dev = _chip_device()
    queue = _queue(lanes=128)
    with obs.enabled() as tr:
        calls = _recording(dev.chip())
        dev.dispatch(queue)
        root = tr.roots[-1]
    assert root.name == "device.dispatch"
    unpacks = root.find("chip.unpack")
    assert len(unpacks) == len(calls) == 2
    for sp in unpacks:
        assert sp.cat == "unpack"
        kids = [(c.name, c.cat) for c in sp.children]
        assert kids[:2] == [("chip.harvest.wait", "wait"),
                            ("chip.harvest.fetch", "fetch")]
        assert kids[2:] and all(k == ("bank.harvest_out", "unpack")
                                for k in kids[2:])
    # the multiplication forwards the addition's planes: the first round
    # is harvested at the stage barrier, before the second is packed
    assert unpacks[0].attrs["barrier"] is True
    assert [sp.cat for sp in root.find("chip.drain")] == ["wait"]
    fetches = root.find("chip.harvest.fetch")
    assert [sp.attrs["bytes"] for sp in fetches] == [
        np.asarray(out).nbytes for _, _, out in calls]
    submits = root.find("chip.submit")
    assert [sp.cat for sp in submits] == ["submit", "submit"]
    h2d = [c for sp in submits for c in sp.children]
    assert [(c.name, c.cat) for c in h2d] == [("chip.h2d", "fetch")] * 2
    assert [c.attrs["bytes"] for c in h2d] == [
        states.nbytes for states, _, _ in calls]
    assert [sp.cat for sp in root.find("chip.account")] == ["account"] * 2
    packs = root.find("chip.pack_round")
    assert sum(sp.attrs["cmds_useful"] for sp in packs) == sum(
        len(cached_table(i.op, i.n_bits, "mig")[1].commands) for i in queue)
    assert [sp.attrs["cmds_replayed"] for sp in packs] == [
        int(np.prod(tables.shape[:3])) for _, tables, _ in calls]
    # every output plane of the queue is converted once, none is vertical
    assert sum(sp.attrs["planes"] for sp in root.find("bank.harvest_out")) \
        == sum(sum(cached_table(i.op, i.n_bits, "mig")[0].out_bits)
               for i in queue)
    # the last round drains with nothing queued behind it: a barrier too
    assert unpacks[-1].attrs["barrier"] is True


def test_double_buffered_round_is_harvested_without_a_barrier():
    rng = np.random.default_rng(1)
    a, b = (rng.integers(0, 256, 128).astype(U) for _ in range(2))
    # more independent bbops than the 2 x 2 slots: two rounds, the
    # first harvested only after the second was submitted
    queue = [BbopInstr("addition", (a, b), 8) for _ in range(6)]
    dev = _chip_device()
    with obs.enabled() as tr:
        dev.dispatch(queue)
        root = tr.roots[-1]
    assert [sp.attrs["barrier"] for sp in root.find("chip.unpack")] == [
        False, True]


def test_harvest_out_counts_only_the_planes_it_converts():
    from repro.core.bank import cached_table

    rng = np.random.default_rng(2)
    a, b = (rng.integers(0, 256, 128).astype(U) for _ in range(2))
    queue = [BbopInstr("addition", (a, b), 8, keep_vertical=True),
             BbopInstr("greater", (a, b), 8)]
    dev = _chip_device()
    with obs.enabled() as tr:
        dev.dispatch(queue)
        root = tr.roots[-1]
    spans = root.find("bank.harvest_out")
    assert spans
    assert sum(sp.attrs["planes"] for sp in spans) == sum(
        cached_table("greater", 8, "mig")[0].out_bits)


def test_pack_wave_counts_only_the_horizontal_planes_it_converts():
    from repro.core.bank import VerticalOperand, cached_table

    rng = np.random.default_rng(3)
    a, b = (rng.integers(0, 256, 128).astype(U) for _ in range(2))
    queue = _queue(lanes=128) + [
        BbopInstr("subtraction", (VerticalOperand.from_values(a, 8), b), 8)]
    dev = _chip_device()
    with obs.enabled() as tr:
        dev.dispatch(queue)
        root = tr.roots[-1]
    spans = root.find("bank.pack_wave")
    assert spans
    # Ref(0) into the multiplication and the vertical operand of the
    # subtraction arrive as planes: 2 + 1 + 2 + 1 horizontal 8-bit operands
    assert sum(sp.attrs["planes"] for sp in spans) == sum(
        w for ins in queue
        for w, o in zip(cached_table(ins.op, ins.n_bits, "mig")[0]
                        .operand_bits, ins.operands)
        if not isinstance(o, (Ref, VerticalOperand))) == 48


def test_chip_spans_reach_the_profiler_host_plane(tmp_path):
    import jax
    from jax.profiler import ProfileData

    dev = _chip_device()
    dev.dispatch(_queue(lanes=128))           # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        with obs.enabled():
            dev.dispatch(_queue(lanes=128))
    files = sorted(tmp_path.rglob("*.xplane.pb"))
    assert files
    data = ProfileData.from_file(str(files[-1]))
    names = {ev.name for plane in data.planes
             if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events}
    assert {"chip.pack_round", "chip.harvest.fetch", "chip.unpack",
            "device.dispatch"} <= names


def test_traced_chip_dispatch_is_bit_identical_and_compiles_nothing():
    from repro.core.control_unit import trace_counts

    plain = _chip_device()
    r_plain = plain.dispatch(_queue(lanes=128, seed=7))
    counts = dict(trace_counts())
    with obs.enabled():
        traced = _chip_device()
        r_traced = traced.dispatch(_queue(lanes=128, seed=7))
    assert dict(trace_counts()) == counts
    assert _exact(r_traced, r_plain)
    assert traced.chip().stats.latency_s == plain.chip().stats.latency_s


_TRANSFER_SPANS = textwrap.dedent("""
    import json
    import numpy as np
    from repro import obs
    from repro.core.bank import BbopInstr, Ref
    from repro.core.chip import SimdramChip
    from repro.core.control_unit import TABLE_CACHE

    rng = np.random.default_rng(0)
    a, b = (rng.integers(0, 256, 256).astype(np.uint64) for _ in range(2))
    queue = [BbopInstr("addition", (a, b), 8),
             BbopInstr("multiplication", (Ref(0), b), 8),
             BbopInstr("greater", (a, b), 8)] * 4
    TABLE_CACHE.clear()
    chip = SimdramChip(n_banks=4, n_subarrays=2)
    with obs.enabled() as tr:
        chip.dispatch(queue)
        root = tr.roots[-1]
    print(json.dumps({name: [sp.attrs for sp in root.find(name)]
                      for name in ("chip.h2d", "chip.harvest.fetch",
                                   "table_cache.miss")}))
""")


def _transfer_spans(devices):
    """The transfer spans' attributes of a chip of four banks on a host
    of ``devices`` CPU devices, from a fresh process."""
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO / "src")
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    out = subprocess.run([sys.executable, "-c", _TRANSFER_SPANS],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stdout + out.stderr
    return json.loads(out.stdout.splitlines()[-1])


@pytest.mark.parametrize("devices", [1, 4])
def test_transfer_spans_count_the_devices_a_round_spans(devices):
    """``chip.h2d`` gives the devices the round's state lands on and the
    bytes each receives, ``bytes`` staying the whole; the copy back and
    each table miss give their devices too."""
    attrs = _transfer_spans(devices)
    h2d = attrs["chip.h2d"]
    assert h2d
    for a in h2d:
        assert a["devices"] == devices
        assert a["devices"] * a["shard_bytes"] == a["bytes"]
    assert [a["devices"] for a in attrs["chip.harvest.fetch"]] == \
        [devices] * len(attrs["chip.harvest.fetch"])
    assert attrs["table_cache.miss"]
    assert all(a["devices"] == devices for a in attrs["table_cache.miss"])
