#!/usr/bin/env python3
"""Readings that set a cell's limits: the program's, and its control's.

    python3 chipbench/control.py --workload <name> --seeds 1,2,3 \\
        --seconds 8 [--control 1,2,3] [--rates 2,3,4] \\
        [--out readings.jsonl]

For each seed, one run of the cell (a short window at the cell's own
load, set-up and check as in ``run.py``) in this one process, and its
compared numbers.  For each ``--control`` seed, the control's readings on
that run's sample as well: the cell's reference priced in the precision
below the configuration's (bfloat16 for the float32 pricing plan, float8
for the bfloat16 model), put in the program's place.  The benchmark's own
runs never run the control.  ``--rates`` repeats every seed at each
offered rate in place of the traffic file's (the sweep that finds a serving
cell's knee), and records the end-to-end metrics too.  Needs the cell's
chips, like ``run.py``.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import harness  # noqa: E402
from chipbench import run as runner  # noqa: E402


def control_reading(driver) -> dict:
    if hasattr(driver, "reference_gaps"):
        res = driver.reference_gaps(driver.sample(), low=True)
        return {"served_gap_std": res["gap_std"], "tokens": res["tokens"]}
    from chipbench.reference.pricing import bf16_round
    err = driver.compare(rnd=bf16_round)
    return {"price_rel_err": err["components"],
            "speedup_rel_err": err["speedup"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", default="")
    ap.add_argument("--rates", default="")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    jax, _ = runner.start_jax()
    try:
        devices = harness.require_devices(jax, cell.chips)
    except harness.NoDevice as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    mod = harness.load_module(
        cell.bench / "drivers" / f"{cell.config['driver']}.py", "cb_driver")
    controls = {int(s) for s in args.control.split(",") if s}
    out = open(args.out, "a") if args.out else None
    rates = [float(r) for r in args.rates.split(",") if r] or [None]
    runs = [(seed, rate) for rate in rates
            for seed in (int(s) for s in args.seeds.split(","))]
    try:
        for seed, rate in runs:
            if rate is not None:
                cell.traffic["rate"] = rate
            ctx = harness.Context(cell=cell, seed=seed, jax=jax,
                                  devices=devices)
            driver = mod.Driver(ctx)
            line = runner.measure(ctx, driver, args.seconds, False,
                                  say=lambda *a: None)
            rec = {"seed": seed, "rate": rate, "correct": line["correct"],
                   "attempted": line["attempted"],
                   "failed": line["failed"],
                   "metrics": {k: v["value"]
                               for k, v in line["metrics"].items()},
                   "program": {k: v["value"]
                               for k, v in line["checks"].items()}}
            waits = ctx.counters.get("admit_wait_s")
            if waits:
                rec["admit_wait_p95_ms"] = harness.percentile(waits, 95) * 1e3
            if seed in controls:
                rec["control"] = control_reading(driver)
            print(json.dumps(rec), flush=True)
            if out:
                out.write(json.dumps(rec) + "\n")
                out.flush()
    finally:
        if out:
            out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
