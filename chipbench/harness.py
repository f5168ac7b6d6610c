"""The benchmark's fixed frame: cell lookup, device check, window, result.

Everything that belongs to one configuration, traffic mix or per-layer
metric lives in a file of its own and is found by name:

* ``BENCHMARK.json`` (checkout root) names the cell, its configuration
  and traffic mix, and the metrics it reports;
* ``chipbench/configs/<config>.json`` holds the sizes and names the
  driver (``chipbench/drivers/<driver>.py``);
* ``chipbench/traffic/<traffic>.json`` holds the mix's parameters;
* ``chipbench/metrics/<metric>.py`` reads one per-layer metric from a
  :class:`Run` (``read(run) -> float | None``).

A driver module defines ``Driver(ctx)`` with ``setup()``, ``window(win)``,
``drain()``, ``end_to_end()``, ``release()`` and ``check()``; see
``drivers/price.py`` for the smallest one.
"""
from __future__ import annotations

import importlib.util
import json
import math
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
OUT = ROOT / "chipbench_out"


class NoDevice(RuntimeError):
    """JAX sees no accelerator, or fewer chips than the cell asks for."""


def load_module(path: Path, name: str):
    """Import one file by path (metric files carry dots in their names)."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    """One entry of ``BENCHMARK.json``'s ``workloads`` with what it names."""

    name: str
    chips: int
    config: dict          # the configuration file's contents
    traffic: dict         # the traffic file's contents
    end_to_end: list      # metric entries this cell reports (trace 0)
    per_layer: list       # metric entries this cell reports (trace 1)
    bench: Path = BENCH   # the tree its driver, traffic and metrics are in


def _reports(metric: dict, cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def load_cell(workload: str, spec_path: Path | None = None) -> Cell:
    spec_path = spec_path or ROOT / "BENCHMARK.json"
    spec = json.loads(spec_path.read_text())
    base = spec_path.parent
    bench = base / BENCH.name
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; BENCHMARK.json "
                         f"has {sorted(cells)}")
    w = cells[workload]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    e2e = [m for m in spec["end_to_end"] if _reports(m, workload)]
    shown = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if (workload in m["workloads"] if "workloads" in m
                     else m["moves"] in shown)]
    return Cell(
        name=workload, chips=int(w["chips"]),
        config=json.loads((base / conf["file"]).read_text()),
        traffic=json.loads((bench / "traffic" / f"{w['traffic']}.json")
                           .read_text()),
        end_to_end=e2e, per_layer=per_layer, bench=bench)


def load_peaks(kind: str) -> dict:
    table = json.loads((BENCH / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise NoDevice(f"no peaks for device kind {kind!r} in peaks.json "
                       f"(known: {sorted(table)})")
    return table[kind]


def require_devices(jax, chips: int):
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoDevice(f"no TPU: JAX's first device is {devs[0].platform} "
                       f"({devs[0].device_kind})")
    if len(devs) < chips:
        raise NoDevice(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return devs[:chips]


def key_from_seed(jax, seed: int):
    """A PRNG key from a seed of up to 63 bits (no 64-bit mode needed)."""
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def percentile(values, q: float) -> float:
    """Linear-interpolated percentile (numpy's default) of a list;
    ``inf`` entries count as infinitely late."""
    xs = sorted(values)
    if not xs:
        return math.nan
    pos = (len(xs) - 1) * q / 100.0
    lo, hi = math.floor(pos), math.ceil(pos)
    if xs[hi] == math.inf:
        return math.inf
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


class CompileCounter:
    """Counts, between ``start()`` and ``stop()``, through
    ``jax.monitoring``: programs compiled by XLA, programs read back from
    the persistent cache instead (JAX times both as a backend compile),
    and functions traced."""

    COMPILE = "/jax/core/compile/backend_compile_duration"
    TRACE = "/jax/core/compile/jaxpr_trace_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self, jax):
        self.on = False
        self.backend = self.hits = self.traces = 0
        jax.monitoring.register_event_duration_secs_listener(self._timed)
        jax.monitoring.register_event_listener(self._event)

    def _timed(self, name, secs, **kw):
        if self.on:
            self.backend += name == self.COMPILE
            self.traces += name == self.TRACE

    def _event(self, name, **kw):
        if self.on:
            self.hits += name == self.HIT

    @property
    def compiles(self) -> int:
        return self.backend - self.hits

    def start(self):
        self.backend = self.hits = self.traces = 0
        self.on = True

    def stop(self):
        self.on = False


class Window:
    """The measured window.  A driver loops ``while win.running():`` and
    does one unit of work per turn; with tracing on, the profiler starts
    ``trace_s`` seconds before the window closes."""

    def __init__(self, seconds: float, tracer=None, trace_s: float = 0.0):
        self.seconds = float(seconds)
        self.tracer = tracer
        self.trace_from = max(0.0, self.seconds - trace_s)
        self.t0 = self.t_end = None

    def open(self):
        self.t0 = time.perf_counter()
        if self.tracer is not None and self.trace_from == 0.0:
            self.tracer.start()

    def elapsed(self) -> float:
        return time.perf_counter() - self.t0

    def running(self) -> bool:
        el = self.elapsed()
        if self.tracer is not None and not self.tracer.started \
                and el >= self.trace_from:
            self.tracer.start()
        if el >= self.seconds:
            if self.t_end is None:
                self.t_end = time.perf_counter()
            return False
        return True

    def close(self):
        """The window's length: from open to the end of its last unit."""
        if self.t_end is None:
            self.t_end = time.perf_counter()
        return self.t_end - self.t0


@dataclass
class Check:
    """One compared number beside its limit (``value <= limit`` passes)."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return bool(self.value <= self.limit)   # nan fails


@dataclass
class Context:
    """What a driver gets: its cell, seed and a place for counters."""

    cell: Cell
    seed: int
    jax: object
    devices: list
    counters: dict = field(default_factory=dict)
    marks: list = field(default_factory=list)   # (phase, end time)

    def say(self, *parts):
        print(*parts, file=sys.stderr, flush=True)

    def mark(self, phase: str):
        """Note that set-up phase ``phase`` ends now (logged by ``run``)."""
        self.marks.append((phase, time.perf_counter()))


@dataclass
class Run:
    """What a per-layer metric reads: the reduced trace, the driver's
    counters, the cell and the chip's peaks."""

    cell: Cell
    counters: dict
    trace: object          # chipbench.trace.Trace or None
    peaks: dict

    def device_events(self, match):
        return [] if self.trace is None else self.trace.device_events(match)


