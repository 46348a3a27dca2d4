"""The four-chip Q6 cell: its configuration, the check that decides
``correct`` on a replay sharded over four devices, and the readers of
the transfer and of the sharded replay.

The whole-harness cases run in a fresh process with four CPU devices
(``XLA_FLAGS=--xla_force_host_platform_device_count=4``), so that the
chip engine picks the sharded executor and the harness's look at the
replay's devices holds the cell to all four."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

from bench.harness import TracedRun
from bench.metrics import h2d_ms, mesh_replay_roofline, replay_balance

from conftest import ROOT

CELL = "q6_sf1_x4"

_RUN = textwrap.dedent("""
    import dataclasses, json, sys
    sys.path[:0] = [{root!r}, {root!r} + "/src", {root!r} + "/bench/tests"]
    import jax
    from bench import harness
    from bench.control import HalfWidthDevice
    from conftest import small_spec

    def one_chip_unreplayed(config):
        # the first chip's 4 bank slabs come back as they were sent
        dev = harness.make_chip_device(config)
        engine = dev.chip()
        plain = engine.executor
        def run(states, tables):
            return plain.run(states, tables).at[:4].set(states[:4])
        engine.executor = dataclasses.replace(plain, run=run)
        return dev

    make = {{"program": harness.make_chip_device,
             "control": lambda config: HalfWidthDevice(),
             "one_chip_unreplayed": one_chip_unreplayed}}[{case!r}]
    spec = small_spec({cell!r})
    spec["cell"]["chips"] = 4
    r = harness.run_cell(spec, 2 ** 31 + 5, 0.3, False, require_chip=False,
                         make_device=make, log=lambda line: None)
    print(json.dumps({{"devices": jax.device_count(), "correct": r["correct"],
                      "failed": r["failed"], "attempted": r["attempted"],
                      "used": r["device"]["count"], "checks": r["checks"]}}))
""")


def _run_case(case):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    script = _RUN.format(root=str(ROOT), case=case, cell=CELL)
    out = subprocess.run([sys.executable, "-c", script], capture_output=True,
                         text=True, env=env, timeout=900)
    assert out.returncode == 0, out.stdout + out.stderr
    return json.loads(out.stdout.splitlines()[-1])


def test_program_is_correct_on_four_devices():
    r = _run_case("program")
    assert r["devices"] == 4 and r["used"] == 4
    assert r["correct"] and r["failed"] == 0 and r["attempted"] > 0


@pytest.mark.parametrize("case", ["control", "one_chip_unreplayed"])
def test_control_and_planted_fault_are_not_correct(case):
    r = _run_case(case)
    assert not r["correct"], r["checks"]
    assert r["failed"] > 0


def test_config_is_the_one_chip_deployment_but_for_its_layout():
    def load(name):
        with open(ROOT / "bench" / "configs" / f"{name}.json") as f:
            return json.load(f)

    one, four = load("tpch_lineitem_sf1"), load("tpch_lineitem_sf1_x4")
    own = {"name", "deployment", "layout"}
    assert {k: v for k, v in four.items() if k not in own} == \
        {k: v for k, v in one.items() if k not in own}
    assert four["name"] == "tpch_lineitem_sf1_x4"
    assert four["layout"] == {"chips": 4, "axis": "data", "banks_per_chip": 4}
    assert four["geometry"]["n_banks"] == 4 * four["layout"]["banks_per_chip"]
    with open(ROOT / "BENCHMARK.json") as f:
        bench = json.load(f)
    cell = {w["name"]: w for w in bench["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "tpch_lineitem_sf1_x4", "q6_stream", 4)
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(pairs) == len(set(pairs))


def _four_devices(replay_ns):
    """A profile of four TPU planes, each with the replay modules whose
    durations ``replay_ns[d]`` lists, and one traced dispatch."""
    from jax.profiler import ProfileData

    from bench import trace

    names = ["bench.dispatch", "jit_chip_replay", "jit_other"]
    meta = "".join(f'event_metadata {{ key: {i + 1} value {{ id: {i + 1} '
                   f'name: "{n}" }} }}\n' for i, n in enumerate(names))

    def line(lid, name, events):
        evs = "".join(f"events {{ metadata_id: {names.index(n) + 1} "
                      f"offset_ps: {int(s * 1000)} "
                      f"duration_ps: {int(d * 1000)} }}\n"
                      for n, s, d in events)
        return f'lines {{ id: {lid} name: "{name}" timestamp_ns: 0\n{evs}}}\n'

    text = ""
    for d, durations in enumerate(replay_ns):
        mods = [("jit_chip_replay", 10 + 1000 * k, ns)
                for k, ns in enumerate(durations)]
        mods.append(("jit_other", 5, 3))
        text += (f'planes {{ id: {d + 1} name: "/device:TPU:{d}"\n'
                 + line(1, "XLA Modules", mods) + meta + "}\n")
    text += ('planes { id: 9 name: "/host:CPU"\n'
             + line(2, "python", [("bench.dispatch", 0, 5000)]) + meta
             + "}\n")
    return trace.from_profile_data(ProfileData.from_text_proto(text))


def _run(**kw):
    base = dict(queues=2, spans=[], profile=None, queue_bytes=0,
                peaks={"hbm_bytes_per_s": 819e9})
    base.update(kw)
    return TracedRun(**base)


def test_replay_balance_and_mesh_roofline_of_four_devices():
    # device time 400, 300, 200, 100 ns: mean 250 over the slowest 400
    prof = _four_devices([[200, 200], [300], [100, 100], [100]])
    run = _run(profile=prof, queue_bytes=819_000)
    assert replay_balance.read(run) == pytest.approx(62.5)
    # 819,000 B over four devices at 819 GB/s: 250 ns, over 400 ns
    assert mesh_replay_roofline.read(run) == pytest.approx(62.5)
    even = _run(profile=_four_devices([[300]] * 4), queue_bytes=819_000)
    assert replay_balance.read(even) == pytest.approx(100.0)
    # a device that replayed nothing: unbalanced, and not in the roofline
    idle = _run(profile=_four_devices([[300], [300], [300], []]),
                queue_bytes=819_000)
    assert replay_balance.read(idle) == pytest.approx(75.0)
    assert mesh_replay_roofline.read(idle) == pytest.approx(
        819_000 / (3 * 819e9) / 300e-9 * 100)


def test_replay_readers_find_nothing_without_a_replay():
    assert replay_balance.read(_run()) is None
    assert mesh_replay_roofline.read(_run()) is None
    silent = _run(profile=_four_devices([[], [], [], []]), queue_bytes=8)
    assert replay_balance.read(silent) is None
    assert mesh_replay_roofline.read(silent) is None
    no_bytes = _run(profile=_four_devices([[300]] * 4))
    assert mesh_replay_roofline.read(no_bytes) is None


def _span(name, cat, wall_s, *children, **attrs):
    from repro.core.telemetry import Span
    return Span(name=name, cat=cat, wall_s=wall_s, attrs=attrs,
                children=list(children))


def test_h2d_ms_is_the_self_time_of_the_state_copies():
    root = _span(
        "device.dispatch", "dispatch", 10.0,
        _span("chip.dispatch", "dispatch", 9.0,
              _span("chip.submit", "submit", 1.0,
                    _span("chip.h2d", "fetch", 0.75, bytes=4_000,
                          devices=4, shard_bytes=1_000)),
              _span("chip.submit", "submit", 0.5,
                    _span("chip.h2d", "fetch", 0.25, bytes=4_000))))
    # 0.75 + 0.25 s over two queues
    assert h2d_ms.read(_run(spans=[root])) == pytest.approx(500.0)
    assert h2d_ms.read(_run(spans=[])) is None
    no_copy = _span("device.dispatch", "dispatch", 1.0,
                    _span("chip.submit", "submit", 1.0))
    assert h2d_ms.read(_run(spans=[no_copy])) is None
