"""The readers of the split harvest, the byte and command counters, and
the alignment of program spans with the profile's clock."""

import pytest

from bench import spans
from bench.harness import TracedRun
from bench.metrics import (cmd_useful_share, d2h_mb, fetch_ms, h2d_mb,
                           idle_unnamed_share, unpack_ms, wait_ms)
from test_trace import _profile

READERS = (wait_ms, fetch_ms, unpack_ms, d2h_mb, h2d_mb, cmd_useful_share,
           idle_unnamed_share)


def _span(name, cat, wall_s, *children, t0=0.0, **attrs):
    from repro.core.telemetry import Span
    return Span(name=name, cat=cat, t0=t0, wall_s=wall_s, attrs=attrs,
                children=list(children))


def _run(spans_, profile=None, queues=2):
    return TracedRun(queues=queues, spans=spans_, profile=profile,
                     queue_bytes=0, peaks={})


def _split_queue():
    """One queue's tree as the chip tier leaves it (seconds of wall)."""
    return _span(
        "device.dispatch", "dispatch", 20.0,
        _span("chip.dispatch", "dispatch", 19.0,
              _span("chip.pack_round", "pack", 3.0,
                    cmds_useful=100, cmds_replayed=400),
              _span("chip.submit", "submit", 0.5,
                    _span("chip.h2d", "fetch", 0.25, bytes=3_000_000)),
              _span("chip.account", "account", 0.75),
              _span("chip.unpack", "unpack", 6.0,
                    _span("chip.harvest.wait", "wait", 1.0),
                    _span("chip.harvest.fetch", "fetch", 0.5,
                          bytes=4_000_000),
                    _span("bank.harvest_out", "unpack", 1.5),
                    _span("bank.harvest_out", "unpack", 2.0),
                    barrier=True),
              _span("chip.pack_round", "pack", 1.0,
                    cmds_useful=50, cmds_replayed=200),
              _span("chip.submit", "submit", 0.5,
                    _span("chip.h2d", "fetch", 0.25, bytes=1_000_000)),
              _span("chip.drain", "wait", 2.0),
              _span("chip.unpack", "unpack", 3.0,
                    _span("chip.harvest.wait", "wait", 0.25),
                    _span("chip.harvest.fetch", "fetch", 0.25,
                          bytes=2_000_000),
                    _span("bank.harvest_out", "unpack", 0.5),
                    barrier=True)))


def _unsplit_queue():
    """The same queue as a program without the split leaves it."""
    return _span(
        "device.dispatch", "dispatch", 20.0,
        _span("chip.dispatch", "dispatch", 19.0,
              _span("chip.pack_round", "pack", 3.0),
              _span("chip.replay", "replay", 0.5),
              _span("chip.drain", "drain", 2.0),
              _span("chip.unpack", "unpack", 6.0)))


def test_span_readers_from_a_tree_by_hand():
    run = _run([_split_queue()], queues=2)
    # waits 1.0 + 0.25 and the drain 2.0, over two queues
    assert wait_ms.read(run) == pytest.approx(1625.0)
    assert fetch_ms.read(run) == pytest.approx(375.0)
    # chip.unpack self 1.0 and 2.0, harvest_out 1.5 + 2.0 + 0.5
    assert unpack_ms.read(run) == pytest.approx(3500.0)
    assert d2h_mb.read(run) == pytest.approx(3.0)
    assert h2d_mb.read(run) == pytest.approx(2.0)
    assert cmd_useful_share.read(run) == pytest.approx(25.0)


@pytest.mark.parametrize("reader", READERS, ids=lambda r: r.__name__)
def test_readers_find_nothing_where_the_program_has_no_split(reader):
    assert reader.read(_run([])) is None
    assert reader.read(_run([_unsplit_queue()])) is None


def _aligned_run():
    """Two dispatches whose root spans start at unrelated perf_counter
    times; the profile holds them at [0, 100) and [200, 300) ns, with
    device work at [20, 30) and [40, 50) inside the first."""
    ns = 1e-9
    first = 1.0
    tree = _span(
        "device.dispatch", "dispatch", 100 * ns,
        _span("chip.dispatch", "dispatch", 90 * ns,
              _span("chip.pack_round", "pack", 30 * ns,
                    t0=first + 10 * ns),
              _span("chip.unpack", "unpack", 40 * ns,
                    _span("chip.harvest.fetch", "fetch", 20 * ns,
                          t0=first + 60 * ns),
                    t0=first + 50 * ns),
              t0=first + 5 * ns),
        t0=first)
    second = _span("device.dispatch", "dispatch", 100 * ns, t0=7.5)
    ops = [("fusion.1", 20, 10), ("fusion.2", 40, 10)]
    profile = _profile(ops, [], [(0, 100), (200, 100)])
    return _run([tree, second], profile=profile)


def test_alignment_names_each_gap_by_the_innermost_span():
    run = _aligned_run()
    pairs = spans.aligned(run)
    assert [w for _, _, w in pairs] == [(0.0, 100.0), (200.0, 300.0)]
    child = run.spans[0].find("chip.harvest.fetch")[0]
    assert spans.interval(child, pairs[0][1]) == pytest.approx((60.0, 80.0))
    idle = spans.idle_by_span(run)
    # the fetch's gap is the fetch's, not its parent's or the root's
    assert idle["chip.harvest.fetch"] == pytest.approx(20.0)
    assert idle["chip.unpack"] == pytest.approx(20.0)       # [50,60), [80,90)
    assert idle["chip.pack_round"] == pytest.approx(20.0)   # [10,20), [30,40)
    assert idle["chip.dispatch"] == pytest.approx(10.0)     # [5,10), [90,95)
    # [0,5) and [95,100), then the whole second dispatch
    assert idle["device.dispatch"] == pytest.approx(110.0)
    assert sum(idle.values()) == pytest.approx(180.0)
    assert idle_unnamed_share.read(run) == pytest.approx(120 / 180 * 100)


def test_alignment_needs_one_root_per_dispatch_annotation():
    run = _aligned_run()
    run.spans = run.spans[:1]
    assert spans.aligned(run) == []
    assert idle_unnamed_share.read(run) is None
    assert idle_unnamed_share.read(_run([_split_queue()])) is None


def test_alignment_maps_both_ends_of_a_dispatch():
    # the host clock runs 1% slow against the profile's: a span in the
    # middle of the dispatch lands in the middle of its annotation
    ns = 1e-9
    child = _span("chip.harvest.fetch", "fetch", 9.9 * ns,
                  t0=50.0 + 49.5 * ns)
    root = _span("device.dispatch", "dispatch", 99 * ns, child, t0=50.0)
    run = _run([root], profile=_profile([], [], [(0, 100)]))
    (_, clock, window), = spans.aligned(run)
    assert window == (0.0, 100.0)
    assert spans.interval(root, clock) == pytest.approx((0.0, 100.0))
    assert spans.interval(child, clock) == pytest.approx((50.0, 60.0))
