"""The benchmark's core: run one cell once and assemble its result line.

Everything here is general.  A cell of ``BENCHMARK.json`` names a
configuration and a traffic mix, and the core finds the rest by those
names:

    bench/configs/<config>.json    the deployment's sizes and parameters
    bench/configs/<config>.py      builds it on the device from the seed,
                                   calls the program, and gives the plain
                                   reference its operator
    bench/traffic/<traffic>.json   the mix: its ``driver`` and parameters
    bench/drivers/<driver>.py      the general generator of that kind
    bench/limits/<cell>.json       the limit of each number compared
    bench/metrics/<metric>.py      one reader per per-layer metric

A driver builds the inputs, warms up, runs the measured window between
:meth:`Context.begin_window` and :meth:`Context.end_window`, lets what the
window left finish, stops the trace (:meth:`Context.close_trace`), reads
the memory peak, frees the program's state, and compares a sample of what
the window produced with the reference.  It returns an :class:`Outcome`; the
core turns that into the cell's end-to-end metrics (``--trace 0``) or,
from the device trace and the counters, its per-layer metrics
(``--trace 1``), and decides ``correct`` from the limits.
"""

from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import math
import os
import shutil
import sys
import time
from typing import Any, Dict, List, Optional

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
CACHE_DIR = os.path.join(ROOT, ".bench_cache")
COMPILE_CACHE = os.path.join(CACHE_DIR, "jax")
TRACE_DIR = os.path.join(CACHE_DIR, "trace")


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    """Import one file by path (names may hold dots, as metric names do)."""
    if not os.path.isfile(path):
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def find_cell(benchmark: dict, name: str) -> dict:
    for cell in benchmark["workloads"]:
        if cell["name"] == name:
            return cell
    known = ", ".join(c["name"] for c in benchmark["workloads"])
    raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has: {known}")


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def configure_compile_cache(path: str = COMPILE_CACHE) -> str:
    """JAX's persistent compilation cache at one fixed directory inside the
    checkout, caching every program however short its compile.  Must run
    before JAX is imported; a directory set by the environment is replaced,
    so two checkouts never share compiled programs."""
    os.makedirs(path, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    import jax

    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def require_chips(chips: int):
    """The devices of a TPU with at least ``chips`` chips, or NoChip."""
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        raise NoChip(f"no TPU found: JAX reports platform {platform!r}")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX found {len(devices)}")
    return devices


@dataclasses.dataclass
class Outcome:
    """What a driver hands back to the core."""

    e2e: Dict[str, float]  # end-to-end metrics measured by the host clock
    attempted: int
    failed: int
    counters: Dict[str, float]  # program counters and work done in the window
    checks: Dict[str, float]  # each number compared, by its name in the limits
    memory_peak_bytes: Optional[int] = None


class Context:
    """What a driver gets: the cell's data and the window's instruments."""

    def __init__(self, *, cell: dict, config: dict, deployment, traffic: dict,
                 seed: int, seconds: float, trace: bool, devices, t_start: float,
                 control: bool = False):
        self.cell, self.config, self.deployment = cell, config, deployment
        self.control = control  # answers of the lower-precision reference stand in
        self.traffic, self.seed, self.seconds = traffic, int(seed), float(seconds)
        self.trace, self.devices, self.t_start = bool(trace), devices, t_start
        self.setup_s: Optional[float] = None
        self.window_s: Optional[float] = None
        self.compiles_in_window = 0
        self._window_span = None
        self._trace_closed = False
        self._counting = False

    # -- time ----------------------------------------------------------------
    @staticmethod
    def now() -> float:
        return time.perf_counter()

    def span(self, name: str):
        """A host span on the profiler's clock (traced runs only)."""
        if not self.trace:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)

    def _on_compile(self, event: str, *args, **kwargs) -> None:
        if self._counting and any(k in event for k in (
                "backend_compile", "cache_retrieval", "jaxpr_trace")):
            self.compiles_in_window += 1

    def begin_window(self) -> float:
        """End set-up and open the measured window; returns its start."""
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._on_compile)
        if self.trace:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
            self._window_span = jax.profiler.TraceAnnotation("bench.window")
            self._window_span.__enter__()
        t0 = self.now()
        self.setup_s = t0 - self.t_start
        self._t0 = t0
        self._counting = True
        return t0

    def end_window(self) -> float:
        """Close the measured window; the trace runs on until
        :meth:`close_trace`, so work the window left (a drain) is traced."""
        t1 = self.now()
        self._counting = False
        import jax

        jax.monitoring.unregister_event_duration_listener(self._on_compile)
        self.window_s = t1 - self._t0
        if self._window_span is not None:
            self._window_span.__exit__(None, None, None)
            self._window_span = None
        return t1

    def close_trace(self) -> None:
        """Stop the profiler (it writes the trace, which takes a while): after
        the window and what it left running, before the comparison."""
        if self.trace and not self._trace_closed:
            import jax

            jax.profiler.stop_trace()
            self._trace_closed = True

    def memory_peak(self) -> Optional[int]:
        """Peak bytes in use on the fullest chip of this process."""
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in self.devices]
        peaks = [p for p in peaks if p is not None]
        return max(peaks) if peaks else None


@dataclasses.dataclass
class Reading:
    """What a per-layer metric reader sees."""

    cell: dict
    config: dict
    traffic: dict
    counters: Dict[str, float]
    trace: Any  # trace_reduce.Summary, or None
    device_kind: str


def _metric_line(specs: List[dict], values: Dict[str, Optional[float]]) -> dict:
    out = {}
    for m in specs:
        v = values.get(m["name"])
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def judge(checks: Dict[str, float], limits: Dict[str, dict]) -> tuple:
    """(correct, {name: {value, limit}}): every number present, finite and
    within its limit, and a limit for every number the driver compared."""
    table, ok = {}, bool(checks)
    for name, spec in limits.items():
        value = checks.get(name)
        table[name] = {"value": value, "limit": spec["limit"]}
        if value is None or not math.isfinite(value) or value > spec["limit"]:
            ok = False
    for name, value in checks.items():
        if name not in limits:
            table[name] = {"value": value, "limit": None}
            ok = False
    return ok, table


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, root: str = ROOT, devices=None,
             overrides: Optional[dict] = None, control: bool = False) -> dict:
    """Run one cell once; returns the result line as a dict.

    ``devices`` None asks JAX for a TPU with the chips the cell needs and
    raises :class:`NoChip` otherwise; tests pass the CPU devices here, and
    ``overrides`` ({"config": {...}, "traffic": {...}}) to shrink the cell.
    ``control`` puts the lower-precision reference's answers in place of the
    program's before the comparison (``bench/readings.py`` and the tests;
    the benchmark's own runs never do).
    """
    benchmark = load_json(os.path.join(root, "BENCHMARK.json"))
    cell = find_cell(benchmark, workload)
    config = load_json(os.path.join(BENCH, "configs", cell["config"] + ".json"))
    deployment = load_module(os.path.join(BENCH, "configs", cell["config"] + ".py"),
                             "bench_config_" + cell["config"].replace(".", "_"))
    traffic = load_json(os.path.join(BENCH, "traffic", cell["traffic"] + ".json"))
    driver = load_module(os.path.join(BENCH, "drivers", traffic["driver"] + ".py"),
                         "bench_driver_" + traffic["driver"])
    limits = load_json(os.path.join(BENCH, "limits", workload + ".json"))
    overrides = overrides or {}
    config = {**config, **overrides.get("config", {})}
    traffic = {**traffic, **overrides.get("traffic", {})}
    if devices is None:
        devices = require_chips(cell["chips"])
    devices = devices[: cell["chips"]]
    ctx = Context(cell=cell, config=config, deployment=deployment, traffic=traffic,
                  seed=seed, seconds=seconds, trace=trace, devices=devices,
                  t_start=t_start, control=control)
    out: Outcome = driver.run(ctx)
    ctx.close_trace()
    log(f"set-up {ctx.setup_s} s, window {ctx.window_s} s, "
        f"compiles inside the window: {ctx.compiles_in_window}")
    for k, v in sorted(out.counters.items()):
        log(f"counter {k}: {v}")

    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(_all_devices(d0)),
              "memory_peak_bytes": out.memory_peak_bytes}
    result: Dict[str, Any] = {}
    if trace:
        import trace_reduce

        summary = trace_reduce.reduce_dir(TRACE_DIR, [d.id for d in devices])
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        reading = Reading(cell=cell, config=config, traffic=traffic,
                          counters=out.counters, trace=summary,
                          device_kind=d0.device_kind)
        specs = [m for m in benchmark["per_layer"] if applies(m, workload)]
        values = {}
        for m in specs:
            reader = load_module(os.path.join(BENCH, "metrics", m["name"] + ".py"),
                                 "bench_metric_" + m["name"].replace(".", "_"))
            values[m["name"]] = reader.read(reading)
        metrics = _metric_line(specs, values)
        result["breakdown"] = summary.breakdown()
    else:
        values = dict(out.e2e, setup_s=ctx.setup_s)
        specs = [m for m in benchmark["end_to_end"] if applies(m, workload)]
        metrics = _metric_line(specs, values)
    correct, table = judge(out.checks, limits)
    result = {"correct": correct, "attempted": out.attempted, "failed": out.failed,
              "metrics": metrics, "device": device, **result,
              "counters": out.counters, "checks": table}
    for name, row in table.items():
        log(f"check {name}: {row['value']!r} against limit {row['limit']!r}")
    return result


def _all_devices(d0):
    import jax

    return [d for d in jax.devices() if d.platform == d0.platform]
