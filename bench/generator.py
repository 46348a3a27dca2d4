"""Traffic generator: every queue of a run, built from ``--seed``.

One general generator serves every cell.  A configuration
(``bench/configs/<name>.json``) states the deployment: the table's
columns with their domains and widths, or the operation set and its
element width, the DRAM geometry and the engine.  A traffic mix
(``bench/traffic/<name>.json``) names a query family and gives its
parameters; the family is the module ``bench/families/<query>.py``,
found by that name.  The seed fixes the data and the order of the
queries; it never changes how much work a block of queries holds, so
the mean cost of a window does not move with the seed.

A family hands out queries in *blocks*: a window ends on a block
boundary, so every window serves whole blocks.  Each query carries its
own parameters, so the reference can judge its answers after the
window; its operands are either kept in the family (the table) or drawn
again from the same per-query seed.
"""

from __future__ import annotations

import datetime
import importlib
from dataclasses import dataclass, field
from typing import Dict, List, Sequence, Tuple

import numpy as np

from bench import reference
from repro.apps.runtime import QueueBuilder as _Builder
from repro.apps.runtime import shard_slices
from repro.core.bank import Ref

STREAM_TABLE, STREAM_WARMUP, STREAM_WINDOW = 0, 1, 2


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """Independent generator for one stream of one run; any whole
    number is a valid seed."""
    return np.random.default_rng(
        np.random.SeedSequence([int(seed) % 2 ** 64, *stream]))


def tpch_retailprice(partkey: np.ndarray) -> np.ndarray:
    """P_RETAILPRICE in cents, as TPC-H 3.0.1 clause 4.2.3 defines it:
    ``(90000 + ((P_PARTKEY / 10) modulo 20001) + 100 * (P_PARTKEY
    modulo 1000)) / 100`` dollars."""
    return 90000 + (partkey // 10) % 20001 + 100 * (partkey % 1000)


def make_column(spec: Dict, rows: int, rng: np.random.Generator,
                columns: Dict[str, np.ndarray]) -> np.ndarray:
    """One integer-coded column: ``uniform`` over ``[lo, hi]``;
    ``sum_uniform``, the sum of independent uniform ``parts`` (TPC-H's
    ship date is the order date plus a uniform delay); or
    ``tpch_extendedprice``, the earlier column ``quantity`` times the
    retail price of a part key uniform over ``partkey``, in cents."""
    if spec["dist"] == "uniform":
        col = rng.integers(spec["lo"], spec["hi"] + 1, size=rows)
    elif spec["dist"] == "sum_uniform":
        col = np.zeros(rows, np.int64)
        for lo, hi in spec["parts"]:
            col += rng.integers(lo, hi + 1, size=rows)
    elif spec["dist"] == "tpch_extendedprice":
        lo, hi = spec["partkey"]
        part = rng.integers(lo, hi + 1, size=rows)
        col = columns[spec["quantity"]] * tpch_retailprice(part)
    else:
        raise ValueError(f"unknown column distribution {spec['dist']!r}")
    col = col.astype(np.int64)
    if col.max(initial=0) > reference.mask(spec["bits"]):
        raise ValueError(f"column exceeds its {spec['bits']} bits")
    return col


@dataclass
class Query:
    """One queue: ``instrs`` go to ``dispatch``; ``params`` is what the
    reference needs to judge the answers; ``out`` says which results
    form the answers."""

    stream: int
    index: int
    params: Dict
    instrs: List = field(repr=False)
    out: Dict[str, List[Tuple[slice, int, int]]] = field(repr=False)
    n_bytes: int = 0


class QueueBuilder:
    """The program's own queue builder, plus the bytes the queue must
    move at the least: each instruction's non-``Ref`` operand bits and
    its output bits, times its lanes, over 8."""

    def __init__(self):
        self._builder = _Builder()
        self.lanes: List[int] = []
        self.n_bytes = 0

    @property
    def instrs(self) -> List:
        return self._builder.queue

    def emit(self, op: str, *operands, n_bits: int):
        ref = self._builder.emit(op, *operands, n_bits=int(n_bits))
        lead = operands[0]
        lanes = (self.lanes[lead.producer] if isinstance(lead, Ref)
                 else int(np.asarray(lead).shape[-1]))
        self.lanes.append(lanes)
        in_w, out_w = reference.widths(op, int(n_bits))
        moved = sum(w for o, w in zip(operands, in_w)
                    if not isinstance(o, Ref)) + sum(out_w)
        self.n_bytes += lanes * moved // 8
        return ref


def assemble(results: Sequence, parts: List[Tuple[slice, int, int]],
             n: int, dtype) -> np.ndarray:
    """One answer of ``n`` lanes from its per-shard ``(lanes, result,
    output)`` parts."""
    out = np.zeros(n, dtype)
    for sl, qi, k in parts:
        r = results[qi]
        out[sl] = np.asarray(r[k] if isinstance(r, tuple) else r)
    return out


def dtype_for(bits: int):
    """The narrowest unsigned numpy type that holds ``bits``."""
    for dt in (np.uint8, np.uint16, np.uint32):
        if bits <= 8 * np.dtype(dt).itemsize:
            return dt
    return np.int64


class Family:
    """A query family: builds queries, pulls their answers out of the
    dispatched results, and judges them against the reference."""

    block = 1
    checks: Tuple[str, ...] = ()

    def __init__(self, config: Dict, mix: Dict, seed: int):
        self.config, self.mix, self.seed = config, mix, int(seed)
        geo = config["geometry"]
        self.units = geo["n_banks"] * geo["subarrays_per_bank"]

    def warmup(self) -> List[Query]:
        return [self.make(STREAM_WARMUP, k)
                for k in range(self.mix["warmup_queries"])]

    def query(self, k: int) -> Query:
        return self.make(STREAM_WINDOW, k)

    def make(self, stream: int, k: int) -> Query:
        raise NotImplementedError

    def collect(self, q: Query, results: Sequence) -> Dict[str, np.ndarray]:
        raise NotImplementedError

    def check(self, q: Query, got: Dict[str, np.ndarray]) -> Dict[str, int]:
        raise NotImplementedError


class Table(Family):
    """A column-resident table generated once per run from the seed."""

    def __init__(self, config, mix, seed):
        super().__init__(config, mix, seed)
        rng = rng_for(seed, STREAM_TABLE)
        self.rows = int(config["rows"])
        self.columns: Dict[str, np.ndarray] = {}
        for name, spec in config["columns"].items():
            self.columns[name] = make_column(spec, self.rows, rng,
                                             self.columns)
        self.bits = {name: int(spec["bits"])
                     for name, spec in config["columns"].items()}
        self.shards = shard_slices(self.rows, self.units)

    def day(self, column: str, date: datetime.date) -> int:
        epoch = datetime.date.fromisoformat(
            self.config["columns"][column]["epoch"])
        return (date - epoch).days


def family(config: Dict, mix: Dict, seed: int) -> Family:
    """The query family a traffic mix names (``bench/families/
    <query>.py``, whose ``FAMILY`` is the class), over its
    configuration."""
    name = str(mix.get("query", ""))
    if not name.isidentifier():
        raise ValueError(f"bad query family name {name!r}")
    try:
        module = importlib.import_module(f"bench.families.{name}")
    except ModuleNotFoundError:
        raise ValueError(f"unknown query family {name!r}")
    return module.FAMILY(config, mix, seed)
