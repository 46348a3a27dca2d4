#!/usr/bin/env python3
"""The control of the check that decides ``correct``, and its readings.

The control is the plain reference put in the program's place and
computed one step of precision below what the configuration states:
every operation evaluated with its operands and outputs held in half
their declared widths (8 bits for 16, 12 for 24), as a later change
that narrowed the datapath would compute.  The benchmark's check has to
find it wrong.

  python3 bench/control.py --workload q6_sf1 --seeds 11 12 13 --seconds 5

runs, in one process on the chip, the program and then the control
through the whole harness on each seed, and prints each run's compared
numbers.  The benchmark's own runs never run the control.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path
from typing import List

import numpy as np


class HalfWidthDevice:
    """Stands in for ``SimdramDevice.dispatch``: evaluates a queue in
    order with :func:`bench.reference.evaluate` at half width."""

    def dispatch(self, queue) -> List:
        from bench import reference

        results: List = []
        for ins in queue:
            operands = []
            for o in ins.operands:
                if hasattr(o, "producer"):          # a Ref to an earlier result
                    r = results[o.producer]
                    o = r[o.out] if isinstance(r, tuple) else r
                operands.append(np.asarray(o))
            outs = reference.evaluate(ins.op, max(1, ins.n_bits // 2),
                                      *operands)
            results.append(outs[0] if len(outs) == 1 else outs)
        return results


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    p.add_argument("--seconds", type=float, default=5.0)
    p.add_argument("--program", type=int, choices=(0, 1), default=1,
                   help="also run the program on each seed")
    args = p.parse_args(argv)

    root = Path(__file__).resolve().parents[1]
    sys.path[:0] = [str(root), str(root / "src")]
    from repro import compile_cache

    from bench import harness

    compile_cache.configure()
    spec = harness.load_cell(args.workload, root)
    sides = ([("program", harness.make_chip_device)] if args.program
             else []) + [("control", lambda config: HalfWidthDevice())]
    for side, make in sides:
        for seed in args.seeds:
            r = harness.run_cell(spec, seed, args.seconds, False,
                                 make_device=make,
                                 log=lambda line: print(line,
                                                        file=sys.stderr))
            print(json.dumps({"side": side, "workload": args.workload,
                              "seed": seed, "correct": r["correct"],
                              "attempted": r["attempted"],
                              "checks": r["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
