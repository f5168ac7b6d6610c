#!/usr/bin/env python3
"""Run one benchmark cell on the chips of this machine.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> \\
        --trace <0|1>

Looks the cell up in ``BENCHMARK.json``, refuses to run without a TPU (or
with fewer chips than the cell asks for), sets up and warms the cell's
driver, measures for ``--seconds``, checks what the window produced
against the plain reference, and prints one JSON line last on standard
output.  With ``--trace 0`` it carries the cell's end-to-end metrics;
with ``--trace 1`` a profiler trace of the window's last part gives the
per-layer metrics, the device's busy time and a breakdown.  The numbers
compared are printed last on standard error and, under ``checks``, last
in the JSON line.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import harness  # noqa: E402
from chipbench.harness import OUT  # noqa: E402


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="also save the reduced trace (gzipped JSON) here")
    return ap.parse_args(argv)


def start_jax():
    """Persistent compile cache inside the checkout (or where
    ``JAX_COMPILATION_CACHE_DIR`` says), every program cached."""
    from repro.launch.compile_cache import use_compile_cache
    cache = use_compile_cache()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax, cache


def memory_peak(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def measure(ctx, driver, seconds: float, trace: bool, keep_trace=None,
            say=print):
    """Set-up, window, drain, memory, check: the part of a run after the
    device check.  Returns the result line as a dict."""
    jax = ctx.jax
    cell = ctx.cell
    counter = harness.CompileCounter(jax)
    counter.start()
    driver.setup()
    counter.stop()
    ctx.mark("driver set-up")
    say(f"setup: {counter.compiles} compiles, {counter.hits} programs read "
        f"from the compile cache, {counter.traces} traces; phases "
        + ", ".join(f"{name} {t - T_START:.3f}"
                    for name, t in ctx.marks) + " s after start")
    tracer = None
    if trace:
        from chipbench.trace import Tracer
        tracer = Tracer(jax, OUT / "trace" / cell.name)
    win = harness.Window(seconds, tracer,
                         float(cell.traffic.get("trace_seconds", seconds)))
    setup_s = time.perf_counter() - T_START
    counter.start()
    win.open()
    driver.window(win)
    window_s = win.close()
    if tracer is not None:
        tracer.stop()
        ctx.counters["trace_t0"] = tracer.t_start
        ctx.counters["trace_t1"] = tracer.t_stop
    driver.drain()
    counter.stop()
    say(f"window {window_s:.3f} s; in the window: {counter.compiles} "
        f"compiles, {counter.hits} programs read from the compile cache, "
        f"{counter.traces} traces; setup {setup_s:.3f} s")
    e2e = driver.end_to_end(window_s)
    e2e["setup_s"] = setup_s
    mem = memory_peak(ctx.devices)
    driver.release()
    gc.collect()

    red = None
    if tracer is not None:
        red = tracer.load()
        if keep_trace:
            from chipbench.trace import save_compact
            save_compact(red.raw, keep_trace)
        shutil.rmtree(tracer.out_dir, ignore_errors=True)

    t_chk = time.perf_counter()
    checks = driver.check()
    say(f"check {time.perf_counter() - t_chk:.3f} s")

    dev = ctx.devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(ctx.devices), "memory_peak_bytes": mem}
    units = {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}
    metrics = {}
    line = {"correct": all(c.ok for c in checks),
            "attempted": driver.attempted, "failed": driver.failed}
    if trace:
        peaks = harness.load_peaks(dev.device_kind)
        run = harness.Run(cell=cell, counters=ctx.counters, trace=red,
                          peaks=peaks)
        for m in cell.per_layer:
            mod = harness.load_module(cell.bench / "metrics" /
                                      f"{m['name']}.py",
                                      f"cb_metric_{len(metrics)}")
            v = mod.read(run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": units[m["name"]]}
        device["busy_s"] = red.busy_ns() * 1e-9
        device["window_s"] = red.window_ns * 1e-9
        line["breakdown"] = {"device_ops": red.top_ops(10),
                             "idle_gaps": red.idle_gaps(10)}
    else:
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    line["metrics"] = metrics
    line["device"] = device
    line["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                      for c in checks}
    for c in checks:
        say(f"check {c.name}: {c.value!r} (limit {c.limit!r}) "
            f"{'ok' if c.ok else 'FAIL'}")
    return line


def main(argv=None) -> int:
    args = parse(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"chipbench: no system under test at {ROOT / 'src'}; "
              "no result", file=sys.stderr)
        return 2
    cell = harness.load_cell(args.workload)
    jax, cache = start_jax()
    t_jax = time.perf_counter()
    try:
        devices = harness.require_devices(jax, cell.chips)
        t_devices = time.perf_counter()
        harness.load_peaks(devices[0].device_kind)
    except harness.NoDevice as e:
        print(f"chipbench: {e}; no result", file=sys.stderr)
        return 2

    def say(*parts):
        print(*parts, file=sys.stderr, flush=True)

    say(f"{cell.name}: {devices[0].device_kind} x {len(devices)}, seed "
        f"{args.seed}, {args.seconds:g} s, trace {args.trace}, cache {cache}")
    driver_mod = harness.load_module(
        cell.bench / "drivers" / f"{cell.config['driver']}.py", "cb_driver")
    ctx = harness.Context(cell=cell, seed=args.seed, jax=jax,
                          devices=devices)
    ctx.marks += [("jax imported", t_jax), ("devices", t_devices)]
    driver = driver_mod.Driver(ctx)
    line = measure(ctx, driver, args.seconds, bool(args.trace),
                   args.keep_trace, say=say)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
