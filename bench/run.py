#!/usr/bin/env python3
"""Run one cell of ``BENCHMARK.json`` on the chip and print its result.

  python3 bench/run.py --workload q6_sf1 --seed 12345 --seconds 51 --trace 0

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics,
or with ``--trace 1`` its per-layer metrics), ``device`` and, last,
``checks``: each number compared with the reference beside its limit.
The same numbers are the last lines of standard error.  Without a TPU,
or with fewer chips than the cell asks for, it exits non-zero and
prints no result.  It must be the only process using the chips.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
import traceback
from pathlib import Path


def main(argv=None) -> int:
    t_start = time.perf_counter()
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)

    root = Path(__file__).resolve().parents[1]
    sys.path[:0] = [str(root), str(root / "src")]
    try:
        from repro import compile_cache

        from bench import harness

        compile_cache.configure()
        spec = harness.load_cell(args.workload, root)
        result = harness.run_cell(
            spec, args.seed, args.seconds, bool(args.trace),
            t_start=t_start,
            log=lambda line: print(line, file=sys.stderr, flush=True))
    except Exception:               # any failure: no result line
        traceback.print_exc()
        print("bench/run.py: FAILED", file=sys.stderr)
        return 1
    for name, c in result["checks"].items():
        print(f"check {name}={c['value']} limit={c['limit']}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
