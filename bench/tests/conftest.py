"""The benchmark's own tests, run by hand on the CPU:

  JAX_PLATFORMS=cpu python -m pytest -q bench/tests

They shrink each cell to a size the CPU runs in seconds and skip the
harness's look for a chip."""

import copy
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

CELLS = ("q6_sf1", "ops16_bulk", "scan_sf1")


def small_spec(cell: str, rows: int = 2048, lanes: int = 1024):
    """The cell as ``BENCHMARK.json`` defines it, at a CPU size."""
    from bench import harness

    spec = copy.deepcopy(harness.load_cell(cell, ROOT))
    if "rows" in spec["config"]:
        spec["config"]["rows"] = rows
    else:
        spec["mix"]["elements"] = lanes
    spec["cell"]["chips"] = 1
    return spec


@pytest.fixture
def spec():
    return small_spec
