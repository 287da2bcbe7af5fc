"""The benchmark harness.

Everything that belongs to one configuration, traffic mix or per-layer
metric sits in a file of its own and is found here by the name that
``BENCHMARK.json`` gives it:

- ``bench/configs/<config>.json``: the configuration as it is run (the
  entry's ``file``);
- ``bench/traffic/<traffic>.json``: the traffic mix, whose ``driver`` key
  names the driver ``bench/drivers/<driver>.py`` that feeds it;
- ``bench/metrics/<metric>.py``: the reader of one per-layer metric;
- ``bench/peaks.json``: the chip's published peaks, by ``device_kind``.

A driver module has three functions, each given the :class:`Run`:
``setup(run) -> state`` builds and warms everything the window uses,
``window(run, state)`` runs the measured window and records the cell's
end-to-end metrics, and ``check(run, state)`` frees the program's state,
runs the plain reference and records the numbers compared with their
limits.  The harness times set-up, reads the peak memory between the window
and the check, reduces the profiler trace and prints the result line.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import os
import shutil
import sys
import time
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

ROOT = Path(__file__).resolve().parents[1]
SPEC_FILE = "BENCHMARK.json"
# the persistent compile cache and the traces live at fixed paths inside the
# checkout (the cache's path is part of its key)
CACHE_DIR = ".jax_cache"
TRACE_DIR = ".bench_trace"


class BenchError(Exception):
    """A cell, file or device the benchmark cannot use."""


# ------------------------------------------------------------ finding pieces
def load_json(path: Path) -> Any:
    if not path.is_file():
        raise BenchError(f"missing file {path}")
    with open(path) as f:
        return json.load(f)


def load_module(path: Path, name: str):
    """Import the module in ``path`` under a private name."""
    if not path.is_file():
        raise BenchError(f"missing file {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    """One entry of ``workloads`` with every piece it names, loaded."""

    name: str
    chips: int
    config_name: str
    config: Dict[str, Any]
    traffic_name: str
    traffic: Dict[str, Any]
    driver: Any
    end_to_end: List[dict]
    per_layer: List[dict]


def resolve(name: str, root: Path = ROOT,
            spec: Optional[dict] = None) -> Cell:
    spec = spec if spec is not None else load_json(root / SPEC_FILE)
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise BenchError(f"no workload {name!r} in {SPEC_FILE}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    if w["config"] not in configs:
        raise BenchError(f"workload {name!r} names no known config")
    config = load_json(root / configs[w["config"]]["file"])
    traffic = load_json(root / "bench" / "traffic" / f"{w['traffic']}.json")
    driver = load_module(root / "bench" / "drivers" / f"{traffic['driver']}.py",
                         f"bench_driver_{traffic['driver']}")
    e2e = [m for m in spec["end_to_end"]
           if name in m.get("workloads", [name])]
    moved = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in moved)]
    return Cell(name, int(w["chips"]), w["config"], config, w["traffic"],
                traffic, driver, e2e, per_layer)


def metric_reader(name: str, root: Path = ROOT):
    return load_module(root / "bench" / "metrics" / f"{name}.py",
                       "bench_metric_" + name.replace(".", "_").replace("-", "_"))


def peaks_for(kind: str, root: Path = ROOT) -> dict:
    table = load_json(root / "bench" / "peaks.json")
    if kind not in table:
        raise BenchError(f"no peaks for device kind {kind!r} in "
                         "bench/peaks.json")
    return table[kind]


# --------------------------------------------------------------------- a run
@dataclasses.dataclass
class Check:
    """One number compared with its limit; ``ok`` is ``value <= limit``."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


@dataclasses.dataclass
class Run:
    """What a driver reads (the cell, seed, window, devices) and writes
    (end-to-end values, counters for the per-layer readers, checks)."""

    cell: Cell
    seed: int
    seconds: float
    trace: bool
    devices: list
    control: bool = False
    fault: Optional[str] = None
    root: Path = ROOT
    log: Callable[[str], None] = lambda msg: print(msg, file=sys.stderr,
                                                   flush=True)
    e2e: Dict[str, float] = dataclasses.field(default_factory=dict)
    counters: Dict[str, Any] = dataclasses.field(default_factory=dict)
    checks: List[Check] = dataclasses.field(default_factory=list)
    attempted: int = 0
    failed: int = 0
    trace_path: Optional[Path] = None
    profiler_s: float = 0.0

    @property
    def config(self) -> dict:
        return self.cell.config

    @property
    def traffic(self) -> dict:
        return self.cell.traffic

    def check(self, name: str, value: float, limit: float) -> None:
        value = float(value)
        if value != value or abs(value) == float("inf"):
            # the result line is JSON: no NaN or infinity; both fail
            value = 1e308 if value != value or value > 0 else -1e308
        self.checks.append(Check(name, value, float(limit)))

    @contextlib.contextmanager
    def profiled(self):
        """Profile the enclosed part of the window when ``--trace 1``;
        the driver decides which part (a whole window can hold more device
        events than a trace should).  Used once per run."""
        if not self.trace:
            yield
            return
        import jax

        from bench.trace import WINDOW_ANNOTATION
        out = self.root / TRACE_DIR / f"{self.cell.name}.{os.getpid()}"
        shutil.rmtree(out, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        t0 = time.perf_counter()
        jax.profiler.start_trace(str(out), profiler_options=opts)
        t1 = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation(WINDOW_ANNOTATION):
                yield
        finally:
            t2 = time.perf_counter()
            jax.profiler.stop_trace()
            # starting and writing the trace is the profiler's, not the
            # program's: a rate in a traced run leaves it out
            self.profiler_s = (t1 - t0) + (time.perf_counter() - t2)
            self.trace_path = out


def chip_devices(chips: int):
    """The first ``chips`` TPU devices; raises when JAX finds no TPU or
    too few of them."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise BenchError(f"JAX found no TPU (platform "
                         f"{devices[0].platform!r})")
    if len(devices) < chips:
        raise BenchError(f"the cell asks for {chips} chips, JAX found "
                         f"{len(devices)}")
    return devices[:chips]


def configure_jax(root: Path = ROOT) -> None:
    """Keep the persistent compile cache at a fixed path in the checkout
    (unless ``JAX_COMPILATION_CACHE_DIR`` names one), and cache every
    program, so that only a cell's first run in a checkout compiles."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(root / CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def memory_peak(devices) -> Optional[int]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return int(max(peaks)) if peaks else None


def execute(run: Run, started: float) -> dict:
    """Set-up, window, memory, check and metrics of one run; returns the
    result object (without printing it)."""
    from bench import trace as trace_lib

    driver = run.cell.driver
    compiles = _CompileCounter()
    state = driver.setup(run)
    run.e2e["setup_s"] = time.perf_counter() - started
    run.log(f"[bench] set-up {run.e2e['setup_s']:.3f} s")
    with compiles.counting():
        driver.window(run, state)
    run.log(f"[bench] programs built inside the window: {compiles.count} "
            f"({compiles.cache_hits} from the persistent cache)")
    run.counters["compiles_in_window"] = compiles.count
    mem = memory_peak(run.devices)
    t0 = time.perf_counter()
    driver.check(run, state)
    del state
    run.log(f"[bench] check {time.perf_counter() - t0:.3f} s")

    dev0 = run.devices[0]
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(run.devices), "memory_peak_bytes": mem}
    result: Dict[str, Any] = {}
    if run.trace:
        summary = None
        if run.trace_path is not None:
            summary = trace_lib.reduce(run.trace_path, run.devices)
            device["busy_s"] = summary.busy_s
            device["window_s"] = summary.window_s
            for dev, busy in zip(run.devices, summary.busy_per_device):
                run.log(f"[bench] device {dev.id}: busy {busy:.6f} s of "
                        f"{summary.window_s:.6f} s")
        metrics = {}
        peaks = run.counters.get("peaks", {})
        for m in run.cell.per_layer:
            value = metric_reader(m["name"], run.root).read(
                run, summary, peaks)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result["metrics"] = metrics
        if summary is not None:
            result["breakdown"] = summary.breakdown()
    else:
        result["metrics"] = {
            m["name"]: {"value": run.e2e[m["name"]], "unit": m["unit"]}
            for m in run.cell.end_to_end if m["name"] in run.e2e}
    ok = bool(run.checks) and all(c.ok for c in run.checks)
    out = {"correct": ok, "attempted": run.attempted, "failed": run.failed}
    out.update(result)
    out["device"] = device
    out["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                     for c in run.checks}
    return out


class _CompileCounter:
    """Counts the programs built while active: backend compile requests,
    and how many of them the persistent cache served."""

    def __init__(self):
        self.count = 0
        self.cache_hits = 0
        self._on = False
        import jax.monitoring

        def on_duration(event, duration, **kw):
            if self._on and event == "/jax/core/compile/backend_compile_duration":
                self.count += 1

        def on_event(event, **kw):
            if self._on and event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    @contextlib.contextmanager
    def counting(self):
        self._on = True
        try:
            yield
        finally:
            self._on = False


def print_result(out: dict) -> None:
    """The numbers compared, beside their limits, as the last lines of
    standard error; the result object as the last line of standard
    output."""
    for name, c in out["checks"].items():
        print(f"[check] {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(f"[check] correct {out['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)


def main(args) -> int:
    started = time.perf_counter()
    try:
        cell = resolve(args.workload)
        if not (ROOT / "src" / "repro").is_dir():
            raise BenchError("the system under test (src/repro) is not in "
                             "this checkout")
        if str(ROOT / "src") not in sys.path:
            sys.path.insert(0, str(ROOT / "src"))
        configure_jax()
        devices = chip_devices(cell.chips)
        peaks = peaks_for(devices[0].device_kind)
    except BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    run = Run(cell=cell, seed=args.seed, seconds=args.seconds,
              trace=bool(args.trace), devices=devices)
    run.counters["peaks"] = peaks
    out = execute(run, started)
    if run.trace_path is not None:
        shutil.rmtree(run.trace_path, ignore_errors=True)
    print_result(out)
    return 0
