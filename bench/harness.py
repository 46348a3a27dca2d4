"""One benchmark cell: set-up, a timed window of queues, the check.

The cell drives the system's normal entry, ``SimdramDevice(backend=
"chip").dispatch``, with queues from :mod:`bench.generator`.  Set-up
generates the data, builds the engine and dispatches the mix's warm-up
queues, which compiles or loads every program the window uses.  The
window then dispatches queues one after the other (one closed-loop
client) until ``seconds`` have passed and the current block is done;
each queue's wall time runs from the ``dispatch`` call to its return,
when the answers are numpy arrays on the host.  Queue building and the
collection of answers lie outside those intervals.  After the window,
every answer of every queue, warm-up included, is compared with the
plain reference (:mod:`bench.reference`).

With ``trace`` on, the first blocks of the window (``TRACE_SECONDS`` of
them, at least one) run under the JAX profiler and the program's
telemetry, and the result carries the per-layer metrics read from them
by ``bench/metrics/<name>.py``.
"""

from __future__ import annotations

import contextlib
import dataclasses
import importlib
import json
import tempfile
import time
from pathlib import Path
from typing import Callable, Dict, List, Optional

from bench import trace as tracing

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
TRACE_SECONDS = 0.5
LOWERING_EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")


class BenchError(RuntimeError):
    """The run cannot give a valid result (no chip, a compile inside the
    window, an executor that is not the one the cell asks for)."""


def _read_json(path: Path) -> Dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, root: Path = ROOT) -> Dict:
    """Everything ``BENCHMARK.json`` and the files it names say about
    one cell: the cell, its configuration and traffic mix, and the
    metrics it reports."""
    bench = _read_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _read_json(root / configs[cell["config"]]["file"])
    mix = _read_json(root / "bench" / "traffic" / f"{cell['traffic']}.json")

    def mine(metrics):
        return [m for m in metrics if name in m.get("workloads", [name])]

    return {"cell": cell, "config": config, "mix": mix,
            "end_to_end": mine(bench["end_to_end"]),
            "per_layer": mine(bench["per_layer"])}


@dataclasses.dataclass
class TracedRun:
    """What a per-layer metric reads: the telemetry span trees of the
    traced dispatches, the reduced profile, and the bytes the traced
    queues must move at the least."""

    queues: int
    spans: List
    profile: Optional[object]
    queue_bytes: int
    peaks: Dict


class CompileWatch:
    """Counts JAX lowerings and backend compiles; a program the window
    needs that set-up did not warm shows up here."""

    def __init__(self):
        import jax
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, _secs: float, **_kw) -> None:
        if event in LOWERING_EVENTS:
            self.count += 1


def make_chip_device(config: Dict):
    """The system under test: ``SimdramDevice`` on the configuration's
    DRAM geometry, with the engine it names (``backend``, ``style``, and
    a ``fault`` model's settings or ``null`` for perfect DRAM)."""
    from repro.core.fault import FaultModel
    from repro.core.isa import SimdramDevice
    from repro.core.timing import DDR4

    geo, eng = config["geometry"], config["engine"]
    cfg = dataclasses.replace(
        DDR4, n_banks=geo["n_banks"],
        subarrays_per_bank=geo["subarrays_per_bank"],
        columns_per_subarray=geo["columns_per_subarray"])
    fault = FaultModel(**eng["fault"]) if eng.get("fault") else None
    return SimdramDevice(cfg=cfg, backend=eng["backend"],
                         style=eng["style"], fault=fault)


def _engine(dev, config: Dict):
    """The device's engine for the configuration's backend, or ``None``
    where ``dev`` is not a ``SimdramDevice`` (the control)."""
    get = getattr(dev, config["engine"]["backend"], None)
    return get() if callable(get) else None


def _peaks(kind: str) -> Dict:
    peaks = _read_json(BENCH / "peaks.json")["devices"]
    if kind not in peaks:
        raise BenchError(f"no peaks for device kind {kind!r} in "
                         "bench/peaks.json")
    return peaks[kind]


def _used_devices(engine, devices: List) -> List:
    ex = getattr(engine, "executor", None)
    if ex is not None and ex.sharded:
        return list(ex.mesh.devices.flat)
    return devices[:1]


@contextlib.contextmanager
def _sharding_watch(engine, chips: int):
    """On a multi-chip cell the engine must pick the sharded executor,
    and the replay outputs of the warm-up must span every chip."""
    if chips == 1 or engine is None:
        yield
        return
    plain = engine.executor
    if not plain.sharded or plain.mesh.devices.size != chips:
        raise BenchError(f"executor is not sharded over {chips} devices: "
                         f"{plain.describe()}")
    spans = []

    def recording(*args):
        out = plain.run(*args)
        spans.append(len(out.sharding.device_set))
        return out

    engine.executor = dataclasses.replace(plain, run=recording)
    try:
        yield
    finally:
        engine.executor = plain
    if not spans or min(spans) != chips:
        raise BenchError(f"replay outputs span {spans} devices, "
                         f"not {chips}")


E2E = {
    "queue_s": lambda walls, setup: sum(walls) / len(walls),
    "setup_s": lambda walls, setup: setup,
}


def run_cell(spec: Dict, seed: int, seconds: float, trace: bool,
             require_chip: bool = True,
             make_device: Callable = make_chip_device,
             t_start: Optional[float] = None,
             log: Callable = print) -> Dict:
    """Run one cell and return its result line as a dict (``checks``
    last).  ``require_chip=False`` skips the look for a TPU (tests drive
    the rest of a run on the CPU); ``make_device`` puts another engine
    in the program's place."""
    t_start = time.perf_counter() if t_start is None else t_start
    import jax

    from bench import generator
    from repro.core import control_unit, telemetry

    cell, config, mix = spec["cell"], spec["config"], spec["mix"]
    chips = int(cell["chips"])
    devices = jax.devices()
    if require_chip:
        if devices[0].platform != "tpu":
            raise BenchError(f"no TPU: default device is "
                             f"{devices[0].platform!r}")
        if len(devices) < chips:
            raise BenchError(f"cell needs {chips} chips, found "
                             f"{len(devices)}")
    watch = CompileWatch()

    # -- set-up: data, engine, every program the window uses ---------------
    fam = generator.family(config, mix, seed)
    dev = make_device(config)
    engine = _engine(dev, config)
    answers = []                     # (query, collected outputs)
    warm = fam.warmup()
    with _sharding_watch(engine, chips):
        for q in warm:
            res = dev.dispatch(q.instrs)
            answers.append((q, fam.collect(q, res)))
            q.instrs = res = None
    used = _used_devices(engine, devices)
    setup_s = time.perf_counter() - t_start
    counts0, lowered0 = control_unit.trace_counts(), watch.count

    # -- the window ----------------------------------------------------------
    walls: List[float] = []
    traced = None
    tracer = None
    trace_dir = tempfile.TemporaryDirectory() if trace else None
    if trace:
        jax.profiler.start_trace(trace_dir.name)
        tracer = telemetry.enable()
        traced = {"queues": 0, "spans": [], "bytes": 0}

    def annotate(name):
        return (jax.profiler.TraceAnnotation(name) if tracer is not None
                else contextlib.nullcontext())

    k = 0
    t_win = time.perf_counter()
    while True:
        for _ in range(fam.block):
            with annotate("bench.build"):
                q = fam.query(k)
            with annotate("bench.dispatch"):
                t0 = time.perf_counter()
                res = dev.dispatch(q.instrs)
                walls.append(time.perf_counter() - t0)
            with annotate("bench.collect"):
                answers.append((q, fam.collect(q, res)))
            if tracer is not None:
                traced["queues"] += 1
                traced["bytes"] += q.n_bytes
                traced["spans"].extend(tracer.roots)
                tracer.reset()
            q.instrs = res = None
            k += 1
        elapsed = time.perf_counter() - t_win
        if tracer is not None and elapsed >= min(TRACE_SECONDS, seconds):
            jax.profiler.stop_trace()
            telemetry.disable()
            tracer = None
        if elapsed >= seconds:
            break
    if tracer is not None:
        jax.profiler.stop_trace()
        telemetry.disable()
    window_s = time.perf_counter() - t_win

    counts1, lowered = control_unit.trace_counts(), watch.count - lowered0
    log(f"[window] queues={len(walls)} window_s={window_s} "
        f"trace_counts_delta={_delta(counts0, counts1)} lowerings={lowered}")
    log(f"[walls] {walls}")
    if lowered or counts1 != counts0:
        raise BenchError(f"{lowered} programs lowered inside the window "
                         f"(trace counts {counts0} -> {counts1})")
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in used)
    device = {"platform": used[0].platform, "kind": used[0].device_kind,
              "count": len(used), "memory_peak_bytes": int(peak)}
    _log_model(engine, log)

    # -- the check -----------------------------------------------------------
    t_check = time.perf_counter()
    totals = {name: 0 for name in fam.checks}
    failed = 0
    n_warm = len(warm)
    for i, (q, got) in enumerate(answers):
        wrong = fam.check(q, got)
        for name, v in wrong.items():
            totals[name] += v
        if i >= n_warm and any(wrong.values()):
            failed += 1
    log(f"[check] queues={len(answers)} seconds="
        f"{time.perf_counter() - t_check}")
    checks = {name: {"value": v, "limit": 0} for name, v in totals.items()}
    correct = all(c["value"] <= c["limit"] for c in checks.values())

    result = {"correct": correct, "attempted": len(walls), "failed": failed}
    if trace:
        run = TracedRun(
            queues=traced["queues"], spans=traced["spans"],
            profile=tracing.load_profile(Path(trace_dir.name)),
            queue_bytes=traced["bytes"],
            peaks=_peaks(device["kind"]) if require_chip else {})
        trace_dir.cleanup()
        metrics = {}
        for m in spec["per_layer"]:
            reader = importlib.import_module(f"bench.metrics.{m['name']}")
            value = reader.read(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result["metrics"] = metrics
        if run.profile is not None and run.profile.window() is not None:
            lo, hi = run.profile.window()
            device["window_s"] = (hi - lo) * 1e-9
            device["busy_s"] = sum(
                tracing.overlap(d.busy(), [(lo, hi)])
                for d in run.profile.devices) \
                / max(1, len(run.profile.devices)) * 1e-9
            result["breakdown"] = tracing.breakdown(run.profile)
        log(f"[trace] queues={run.queues} bytes={run.queue_bytes}")
    else:
        result["metrics"] = {
            m["name"]: {"value": E2E[m["name"]](walls, setup_s),
                        "unit": m["unit"]}
            for m in spec["end_to_end"]}
    result["device"] = device
    result["checks"] = checks
    return result


def _delta(a: Dict[str, int], b: Dict[str, int]) -> Dict[str, int]:
    return {k: b[k] - a.get(k, 0) for k in b}


def _log_model(engine, log: Callable) -> None:
    """The modeled DRAM statistics, for reading only: they are the
    simulator's output, not its speed, and take no part in ``correct``."""
    stats = getattr(engine, "stats", None)
    if stats is None:
        return
    fields = ("rounds", "batches", "latency_s", "wall_s", "pack_wall_s")
    log("[model] " + " ".join(f"{f}={getattr(stats, f, None)}"
                              for f in fields))
