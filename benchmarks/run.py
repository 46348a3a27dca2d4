"""Benchmark harness entrypoint: one function per paper table.

Prints ``name,us_per_call,derived`` CSV rows.  Modes:

  python -m benchmarks.run              # all paper tables (fast settings)
  python -m benchmarks.run --table X    # one table
  python -m benchmarks.run --full       # larger trial counts / widths
  python -m benchmarks.run --smoke      # tiny shapes (the CI app gate)

Roofline/dry-run benchmarks for the LM stack live in benchmarks/roofline.py
(they need the 512-device env var and are invoked via repro.launch.dryrun).
"""

from __future__ import annotations

import argparse
import sys
import time

from repro import compile_cache

from . import bank_scaling as B
from . import chip_scaling as C
from . import fault_sweep as F
from . import paper_tables as T
from . import serving_soak as S

TABLES = {
    "serving": lambda full, smoke=False: S.table_serving_soak(
        loads=(8, 32) if full else (4, 12),
        sigmas=(0.0, 0.12, 0.15) if full else (0.0, 0.15),
        rounds=6 if full else 3,
        lanes=128 if full else 32,
        p_trials=200_000 if full else 20_000,
        out_json=None),
    "fault_sweep": lambda full, smoke=False: F.table_fault_sweep(
        sigmas=(0.12, 0.15, 0.18) if full else (0.15, 0.18),
        spare_lanes=(1, 2) if full else (1,),
        lanes=256 if full else 128,
        p_trials=200_000 if full else 50_000,
        out_json=None),
    "chip_scaling": lambda full, smoke=False: C.table_chip_scaling(
        lanes=65536 if full else 4096,
        n_instrs=32 if full else 16,
        out_json=None),
    "throughput": lambda full, smoke=False: T.table_throughput(widths=(8, 16, 32) if full else (8, 16, 32)),
    "bank_scaling": lambda full, smoke=False: B.table_bank_scaling(
        widths=(8, 16, 32) if full else (8, 16),
        lanes=65536 if full else 4096),
    "hetero_dispatch": lambda full, smoke=False: B.table_hetero_dispatch(
        lanes=65536 if full else 4096,
        n_instrs=32 if full else 16,
        out_json=None),
    "energy": lambda full, smoke=False: T.table_energy(),
    "synthesis": lambda full, smoke=False: T.table_synthesis(widths=(8, 16) if not full else (8, 16, 32)),
    "area": lambda full, smoke=False: T.table_area(),
    "reliability": lambda full, smoke=False: T.table_reliability(200_000 if full else 50_000),
    "apps": lambda full, smoke=False: T.table_apps(
        mode="smoke" if smoke else ("full" if full else "fast")),
}


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--table", choices=sorted(TABLES), default=None)
    p.add_argument("--full", action="store_true")
    p.add_argument("--smoke", action="store_true",
                   help="tiny shapes; used by scripts/ci.sh for the apps "
                        "bit-exactness gate")
    args = p.parse_args()
    compile_cache.configure()

    t0 = time.time()
    names = [args.table] if args.table else list(TABLES)
    for name in names:
        print(f"\n## {name}")
        TABLES[name](args.full, args.smoke)
    print(f"\n# total_wall_s,{time.time() - t0:.1f},0")


if __name__ == "__main__":
    main()
