"""Profiler trace: record one, reduce it to device events and host spans.

:class:`Tracer` wraps ``jax.profiler`` with the Python tracer off (only
the harness's own ``TraceAnnotation`` spans and JAX's dispatch events are
kept on the host).  :func:`load_xplane` turns the written ``.xplane.pb``
into plain lists; :class:`Trace` answers what the per-layer metrics ask:
device events by name, device busy time (the union of op intervals), and
idle gaps labelled by the host span that was open in them.

Device planes are ``/device:TPU:<n>``.  Their op line (``XLA Ops``) gives
the busy time; their module line (``XLA Modules``) gives one event per
executed program, named after the jitted function.
"""
from __future__ import annotations

import gzip
import json
import shutil
from dataclasses import dataclass
from pathlib import Path

OP_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
HOST_LINE = "python"
#: host spans the harness writes start with this prefix
SPAN_PREFIX = "cb."


class Tracer:
    """Start and stop one profiler session into ``out_dir``."""

    def __init__(self, jax, out_dir: Path):
        self.jax = jax
        self.out_dir = Path(out_dir)
        self.started = self.stopped = False
        self.t_start = self.t_stop = None

    def start(self):
        import time
        shutil.rmtree(self.out_dir, ignore_errors=True)
        opts = self.jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        self.jax.profiler.start_trace(str(self.out_dir),
                                      profiler_options=opts)
        self.started = True
        self.t_start = time.perf_counter()

    def stop(self):
        import time
        if self.started and not self.stopped:
            self.t_stop = time.perf_counter()
            self.jax.profiler.stop_trace()
            self.stopped = True

    def load(self) -> "Trace":
        paths = sorted(self.out_dir.glob("**/*.xplane.pb"))
        if not paths:
            raise FileNotFoundError(f"no .xplane.pb under {self.out_dir}")
        return Trace(load_xplane(paths[-1]))


def load_xplane(path) -> dict:
    """``{"planes": [{"name", "lines": [{"name", "events": [[name,
    start_ns, dur_ns], ...]}]}]}``: the device planes' op and module
    lines, and one host plane (``/host:CPU``, line ``python``) with the
    harness's ``cb.*`` spans from whichever host line holds them.  Op
    names are cut to the HLO instruction's name."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    planes, spans = [], []
    for plane in pd.planes:
        if not plane.name.startswith("/device:"):
            spans += [[e.name, float(e.start_ns), float(e.duration_ns)]
                      for line in plane.lines for e in line.events
                      if e.name.startswith(SPAN_PREFIX)]
            continue
        lines = [{"name": line.name, "events": [
            [e.name.split(" = ")[0], float(e.start_ns), float(e.duration_ns)]
            for e in line.events]}
            for line in plane.lines if line.name in (OP_LINE, MODULE_LINE)]
        planes.append({"name": plane.name, "lines": lines})
    planes.append({"name": HOST_PLANE, "lines": [
        {"name": HOST_LINE, "events": sorted(spans, key=lambda e: e[1])}]})
    return {"planes": planes}


def save_compact(data: dict, path) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(data, f)


def load_compact(path) -> dict:
    with gzip.open(path, "rt") as f:
        return json.load(f)


def _union(intervals):
    """Merged, sorted ``[start, end]`` intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _overlap(merged, lo, hi) -> float:
    return sum(max(0.0, min(e, hi) - max(s, lo)) for s, e in merged)


@dataclass
class Event:
    name: str
    start: float          # ns
    dur: float            # ns

    @property
    def end(self) -> float:
        return self.start + self.dur


class Trace:
    """A reduced trace: device op and module events per device plane,
    and the harness's host spans (names starting with ``cb.``)."""

    def __init__(self, data: dict):
        self.raw = data
        self.ops, self.modules, self.spans = {}, {}, []
        for plane in data["planes"]:
            for line in plane["lines"]:
                evs = [Event(*e) for e in line["events"]]
                if plane["name"] == HOST_PLANE:
                    self.spans += [e for e in evs
                                   if e.name.startswith(SPAN_PREFIX)]
                elif line["name"] == OP_LINE:
                    self.ops[plane["name"]] = evs
                elif line["name"] == MODULE_LINE:
                    self.modules[plane["name"]] = evs
        self.devices = sorted(set(self.ops) | set(self.modules))
        if not self.devices:
            raise ValueError("the trace holds no device plane")
        self._busy = {d: _union([(e.start, e.end)
                                 for e in self.ops.get(d) or
                                 self.modules.get(d, [])])
                      for d in self.devices}
        every = [iv for d in self.devices for iv in self._busy[d]]
        every += [(s.start, s.end) for s in self.spans]
        self.t0 = min(iv[0] for iv in every)
        self.t1 = max(iv[1] for iv in every)

    # ---------------------------------------------------------- time
    @property
    def window_ns(self) -> float:
        return self.t1 - self.t0

    def busy_ns(self, lo=None, hi=None) -> float:
        """Busy time averaged over the traced devices, within
        ``[lo, hi]`` (default: the whole trace)."""
        lo = self.t0 if lo is None else lo
        hi = self.t1 if hi is None else hi
        return sum(_overlap(self._busy[d], lo, hi)
                   for d in self.devices) / len(self.devices)

    def idle_share(self) -> float:
        return 1.0 - self.busy_ns() / self.window_ns

    # ---------------------------------------------------------- events
    def device_events(self, match, line: str = MODULE_LINE) -> list:
        """Events of the first device whose name contains ``match`` (a
        string) or satisfies it (a callable)."""
        test = match if callable(match) else (lambda n: match in n)
        src = self.modules if line == MODULE_LINE else self.ops
        evs = src.get(self.devices[0], [])
        return [e for e in evs if test(e.name)]

    def spans_named(self, name: str) -> list:
        return [s for s in self.spans if s.name == name]

    # ---------------------------------------------------------- breakdown
    def top_ops(self, n: int = 10) -> list:
        """``[[op name, seconds], ...]``: device time per op name, summed
        over the first device's op line, largest first."""
        d = self.devices[0]
        tot = {}
        for e in self.ops.get(d) or self.modules.get(d, []):
            tot[e.name] = tot.get(e.name, 0.0) + e.dur
        return [[k, v * 1e-9] for k, v in
                sorted(tot.items(), key=lambda kv: -kv[1])[:n]]

    def idle_gaps(self, n: int = 10) -> list:
        """``[[label, seconds], ...]``: the longest gaps between device
        activity on the first device, each labelled by the innermost host
        span open at its midpoint (``host`` where none is)."""
        busy = self._busy[self.devices[0]]
        edges = [self.t0] + [x for iv in busy for x in iv] + [self.t1]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        gaps.sort(key=lambda g: g[0] - g[1])
        out = []
        for lo, hi in gaps[:n]:
            mid = 0.5 * (lo + hi)
            open_ = [s for s in self.spans if s.start <= mid <= s.end]
            label = min(open_, key=lambda s: s.dur).name if open_ \
                else "host"
            out.append([label, (hi - lo) * 1e-9])
        return out
