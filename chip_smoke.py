#!/usr/bin/env python3
"""Smoke run of the SIMDRAM dispatch path on a TPU host.

  python chip_smoke.py               # one chip: the phases below
  python chip_smoke.py --four-chips  # only the sharded executors on 4 chips

Phases on one chip, each verified and each fatal on failure:

  1. device   — the default JAX device must be a TPU (no CPU fallback);
  2. q6       — TPC-H Q6 over a scale-factor-1 lineitem table (6,001,215
                rows) through ``SimdramDevice(backend="chip").dispatch`` on
                the default DDR4 geometry (16 banks x 65,536 columns), run
                twice: the query checks itself against its numpy oracle,
                and the warm run must compile nothing new;
  3. ops      — one queue holding all 16 ops at 8 bits over 65,536 lanes
                each, with a ``Ref`` chain, a ``VerticalOperand`` input
                (h2v transposition kernel) and a ``keep_vertical`` output
                (v2h kernel), checked against every op's oracle;
  4. fault    — the same queue on a fault-injected chip engine (sigma
                0.12, one spare lane): results bit-exact, no host fallback.
                At sigma 0.15 the two replicas of a lane are sometimes
                wrong in the same way, which no vote can detect: for most
                seeds a handful of the queue's 1.1M lanes come back wrong,
                so this phase does not check sigma 0.15.

``--four-chips`` dispatches the Q6 queue on ``SimdramChip`` (1-D ``data``
mesh over 4 devices) and ``SimdramChannel`` (2x2 ``(channel, data)``
mesh) with ``use_shard_map=True``, and compares each bit-exactly with the
same engine on one device and with the oracle.

The last line of standard output is one JSON object naming the device;
any failure exits non-zero without printing it.  Everything runs in this
one process, which must be the only one using the chips.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
import traceback
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

SF1_ROWS = 6_001_215
OPS_LANES = 65_536
OPS_BITS = 8
FAULT_SIGMA = 0.12


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, message: str) -> None:
    if not ok:
        raise SmokeFailure(message)


def log(phase: str, **fields) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


def tpu_devices():
    import jax

    devs = jax.devices()
    check(devs[0].platform == "tpu",
          f"no TPU: default device is {devs[0].platform!r}")
    return devs


def peak_bytes(device) -> int:
    stats = device.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


# -- TPC-H Q6 at scale factor 1 ----------------------------------------------

def phase_q6(devs) -> None:
    import jax

    from repro.apps import tpch
    from repro.core import control_unit
    from repro.core.isa import SimdramDevice

    dev = SimdramDevice(backend="chip")
    counts = None
    for run in ("cold", "warm"):
        stats = dev.chip().stats
        rounds0, replays0 = stats.rounds, stats.batches
        t0 = time.perf_counter()
        r = tpch.run(n_rows=SF1_ROWS, device=dev)
        # results are host arrays already; the barrier keeps the timing
        # honest should that ever change
        jax.block_until_ready(r["output"])
        wall = time.perf_counter() - t0
        now = control_unit.trace_counts()
        if run == "warm":
            check(now == counts, f"warm Q6 run compiled: {counts} -> {now}")
        counts = now
        stats = dev.chip().stats
        log("q6", run=run, rows=r["rows"], wall_s=wall,
            selected=r["selected"], revenue=r["revenue"],
            rounds=stats.rounds - rounds0, replays=stats.batches - replays0,
            peak_bytes_in_use=peak_bytes(devs[0]), trace_counts=now)


# -- all 16 ops in one queue -------------------------------------------------

def ops_queue(lanes: int = OPS_LANES, seed: int = 0):
    """One instruction per op; ``subtraction`` takes ``addition``'s result
    through a ``Ref``, ``max`` takes a ``VerticalOperand`` and
    ``multiplication`` keeps its result vertical.  Returns the queue and
    the oracle's expected outputs per instruction."""
    import numpy as np

    from repro.core.bank import BbopInstr, Ref, VerticalOperand
    from repro.core.ops_library import ALL_OPS, get_op

    rng = np.random.default_rng(seed)
    queue, want = [], []
    produced = {}
    for op in ALL_OPS:
        spec = get_op(op, OPS_BITS)
        vals = [rng.integers(0, 1 << w, lanes).astype(np.int64)
                for w in spec.operand_bits]
        operands = list(vals)
        if op == "subtraction":
            qi, out = produced["addition"]
            operands[0] = Ref(qi)
            vals[0] = out & ((1 << spec.operand_bits[0]) - 1)
        if op == "max":
            operands[0] = VerticalOperand.from_values(
                vals[0], spec.operand_bits[0])
        exp = spec.oracle(*[v.astype(np.uint64) for v in vals])
        produced[op] = (len(queue), exp[0].astype(np.int64))
        queue.append(BbopInstr(op, tuple(operands), OPS_BITS,
                               keep_vertical=(op == "multiplication")))
        want.append(exp)
    return queue, want


def check_ops(queue, results, want) -> None:
    import numpy as np

    from repro.core.bank import VerticalOperand, flatten_result
    from repro.core.ops_library import get_op

    for ins, res, exp in zip(queue, results, want):
        if ins.keep_vertical:
            check(isinstance(res, VerticalOperand),
                  f"{ins.op}: keep_vertical result is {type(res).__name__}")
        spec = get_op(ins.op, ins.n_bits)
        for w, got, e in zip(spec.out_bits, flatten_result(res), exp):
            mask = (1 << w) - 1
            check(np.array_equal(np.asarray(got).astype(np.int64) & mask,
                                 np.asarray(e).astype(np.int64) & mask),
                  f"{ins.op}: result differs from its oracle")


def phase_ops(queue, want) -> None:
    from repro.core.isa import SimdramDevice

    dev = SimdramDevice(backend="chip")
    t0 = time.perf_counter()
    results = dev.dispatch(queue)
    check_ops(queue, results, want)
    stats = dev.chip().stats
    log("ops", instrs=len(queue), lanes=OPS_LANES,
        wall_s=time.perf_counter() - t0, rounds=stats.rounds,
        replays=stats.batches, verified=True)


def phase_fault(queue, want) -> None:
    from repro.core.fault import FaultModel
    from repro.core.isa import SimdramDevice

    dev = SimdramDevice(backend="chip",
                        fault=FaultModel(sigma=FAULT_SIGMA, spare_lanes=1))
    t0 = time.perf_counter()
    results = dev.dispatch(queue)
    check_ops(queue, results, want)
    faults = dev.chip().stats.faults
    check(faults.host_fallbacks == 0,
          f"{faults.host_fallbacks} answers fell back to the host")
    log("fault", wall_s=time.perf_counter() - t0, injected=faults.injected,
        detected=faults.detected, corrected=faults.corrected,
        retries=faults.retries, host_fallbacks=faults.host_fallbacks)


# -- four chips: the sharded executors ---------------------------------------

def _record_outputs(engine) -> list:
    """Wrap ``engine.executor.run`` so each replay output is kept."""
    outs = []
    run = engine.executor.run

    def recording_run(*args):
        out = run(*args)
        outs.append(out)
        return out

    engine.executor = dataclasses.replace(engine.executor, run=recording_run)
    return outs


def _same(a, b) -> bool:
    import numpy as np

    from repro.core.bank import flatten_result

    return all(
        all(np.array_equal(x, y)
            for x, y in zip(flatten_result(ra), flatten_result(rb)))
        for ra, rb in zip(a, b))


def phase_four_chips(devs) -> int:
    from repro.apps import tpch
    from repro.core.channel import SimdramChannel
    from repro.core.chip import SimdramChip
    from repro.core.timing import DDR4

    check(len(devs) >= 4, f"--four-chips needs 4 devices, found {len(devs)}")
    subs = DDR4.subarrays_per_bank
    queue, finish = tpch.q6_queue(SF1_ROWS, DDR4.n_banks * subs)
    tiers = {
        "chip": lambda sm: SimdramChip(n_banks=DDR4.n_banks, n_subarrays=subs,
                                       use_shard_map=sm),
        "channel": lambda sm: SimdramChannel(
            n_chips=2, n_banks=DDR4.n_banks, n_subarrays=subs,
            use_shard_map=sm),
    }
    used = set()
    for tier, make in tiers.items():
        single = make(False)
        check(not single.executor.sharded, f"{tier}: reference is sharded")
        t0 = time.perf_counter()
        ref = single.dispatch(queue)
        ref_wall = time.perf_counter() - t0
        answer = finish(ref)
        sharded = make(True)
        check(sharded.executor.sharded, f"{tier}: executor is not sharded")
        outs = _record_outputs(sharded)
        t0 = time.perf_counter()
        got = sharded.dispatch(queue)
        wall = time.perf_counter() - t0
        check(_same(got, ref), f"{tier}: sharded results differ from one "
              "device")
        finish(got)
        devices = outs[-1].sharding.device_set
        check(len(devices) == len(devs),
              f"{tier}: replay output on {len(devices)} devices")
        used |= devices
        log("four_chips", tier=tier,
            mesh=dict(sharded.executor.mesh.shape),
            replay_devices=sorted(d.id for d in devices),
            sharded_wall_s=wall, single_device_wall_s=ref_wall,
            selected=answer["selected"], revenue=answer["revenue"],
            bit_exact=True)
    return len(used)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--four-chips", action="store_true",
                   help="run only the sharded chip/channel executors on "
                        "four devices and what they are compared with")
    args = p.parse_args(argv)

    from repro import compile_cache

    cache_dir = compile_cache.configure()
    try:
        devs = tpu_devices()
        log("device", platform=devs[0].platform, kind=devs[0].device_kind,
            count=len(devs), compile_cache=cache_dir)
        if args.four_chips:
            count = phase_four_chips(devs)
        else:
            phase_q6(devs)
            queue, want = ops_queue()
            phase_ops(queue, want)
            phase_fault(queue, want)
            count = len(devs)
    except Exception:               # any failed phase: no result line
        traceback.print_exc()
        print("chip_smoke: FAILED", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
