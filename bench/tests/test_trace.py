"""The reductions from spans and traces to per-layer metrics."""

import pytest

from bench import trace
from bench.generator import QueueBuilder
from bench.harness import TracedRun
from bench.metrics import idle_share, pack_ms, plan_ms, replay_ms, \
    replay_roofline


def _span(name, cat, wall_s, *children):
    from repro.core.telemetry import Span
    return Span(name=name, cat=cat, wall_s=wall_s, children=list(children))


def _run(**kw):
    base = dict(queues=1, spans=[], profile=None, queue_bytes=0,
                peaks={"hbm_bytes_per_s": 819e9})
    base.update(kw)
    return TracedRun(**base)


def test_self_time_from_a_span_tree():
    root = _span(
        "device.dispatch", "dispatch", 10.0,
        _span("device.validate", "plan", 1.0),
        _span("chip.dispatch", "dispatch", 8.5,
              _span("chip.plan", "plan", 0.5),
              _span("chip.schedule", "plan", 1.0),
              _span("chip.pack_round", "pack", 3.0,
                    _span("bank.pack_wave", "pack", 2.0)),
              _span("chip.unpack", "unpack", 2.5)))
    assert trace.self_time(root) == pytest.approx(0.5)
    assert trace.self_time(root.children[1].children[2]) == pytest.approx(1.0)
    run = _run(queues=2, spans=[root])
    # 0.5 (dispatch self) + 1.0 + 0.5 + 1.0, over two queues
    assert plan_ms.read(run) == pytest.approx(1500.0)
    # 1.0 (pack_round self) + 2.0, over two queues
    assert pack_ms.read(run) == pytest.approx(1500.0)
    assert plan_ms.read(_run(spans=[])) is None


def _xspace(device_ops, device_modules, dispatches):
    """A small XSpace: one TPU plane with op and module lines, one host
    plane with the harness's dispatch annotations (times in ns)."""
    names = sorted({n for n, _, _ in device_ops + device_modules}
                   | {"bench.dispatch"})
    ids = {n: i + 1 for i, n in enumerate(names)}
    meta = "".join(f'event_metadata {{ key: {i} value {{ id: {i} '
                   f'name: "{n}" }} }}\n' for n, i in ids.items())

    def line(lid, name, events):
        evs = "".join(f"events {{ metadata_id: {ids[n]} offset_ps: "
                      f"{int(s * 1000)} duration_ps: {int(d * 1000)} }}\n"
                      for n, s, d in events)
        return (f'lines {{ id: {lid} name: "{name}" timestamp_ns: 0\n'
                f'{evs}}}\n')

    return ("planes { id: 1 name: \"/device:TPU:0\"\n"
            + line(1, "XLA Ops", device_ops)
            + line(2, "XLA Modules", device_modules) + meta + "}\n"
            + "planes { id: 2 name: \"/host:CPU\"\n"
            + line(3, "python", [("bench.dispatch", s, d)
                                 for s, d in dispatches]) + meta + "}\n")


def _profile(device_ops, device_modules, dispatches):
    from jax.profiler import ProfileData
    data = ProfileData.from_text_proto(
        _xspace(device_ops, device_modules, dispatches))
    return trace.from_profile_data(data)


def test_busy_union_and_idle_share_from_a_recorded_profile():
    # dispatches cover [0, 100) and [200, 300): 200 ns in all; one op
    # nests in another and one lies outside every dispatch
    ops = [("fusion.1", 10, 30), ("fusion.2", 20, 10), ("copy.3", 210, 40),
           ("fusion.1", 150, 20)]
    modules = [("jit_chip_replay", 10, 40), ("jit_chip_replay", 210, 40)]
    prof = _profile(ops, modules, [(0, 100), (200, 100)])
    dev = prof.devices[0]
    assert trace.union((s, e) for _, s, e in dev.ops) == [
        (10.0, 40.0), (150.0, 170.0), (210.0, 250.0)]
    assert trace.busy_within(dev, prof.host["bench.dispatch"]) == 70.0
    assert prof.window() == (0.0, 300.0)
    run = _run(queues=2, profile=prof)
    assert idle_share.read(run) == pytest.approx(65.0)   # 1 - 70/200
    assert replay_ms.read(run) == pytest.approx(40e-6)   # 80 ns / 2 queues
    ops = trace.breakdown(prof)["device_ops"]
    # fusion.2 lies inside fusion.1's event and is not counted again
    assert ops == [["fusion.1", pytest.approx(50e-9)],
                   ["copy.3", pytest.approx(40e-9)]]
    gaps = trace.breakdown(prof)["idle_gaps"]
    assert [g[0] for g in gaps][:2] == ["bench.dispatch", "bench.dispatch"]
    assert sum(g[1] for g in gaps) == pytest.approx(300e-9 - 90e-9)


def test_replay_roofline_byte_count_of_a_known_queue():
    import numpy as np
    qb = QueueBuilder()
    x = np.arange(64)
    a = qb.emit("greater_equal", x, x, n_bits=12)     # 24 in + 1 out
    b = qb.emit("multiplication", x, x, n_bits=14)    # 28 in + 28 out
    qb.emit("if_else", a, b, x, n_bits=28)            # 28 in (two Refs) + 28
    assert qb.n_bytes == 64 * (25 + 56 + 56) // 8
    prof = _profile([], [("jit_chip_replay", 0, 1e6)], [(0, 2e6)])
    run = _run(queues=1, profile=prof, queue_bytes=qb.n_bytes)
    least = qb.n_bytes / 819e9
    assert replay_roofline.read(run) == pytest.approx(least / 1e-3 * 100)
