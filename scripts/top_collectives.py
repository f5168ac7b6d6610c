"""Print the top collectives by total wire bytes for a dry-run cell, and
(optionally) how stable each one's message-free verdict is across a CXL
latency-band scenario sweep.

Usage: PYTHONPATH=src python scripts/top_collectives.py HLO.gz [N] [--sweep]
           [--backend=SPEC] [--chunk=K]

``--backend=`` takes the ``ExecPlan.parse`` spec form — a registered
backend name plus optional options, e.g. ``--backend=jax``,
``--backend=pallas:interpret=1`` (interpret the kernel even on a TPU),
``--backend=jax:vmap=1``; ``--chunk=K`` bounds peak memory to K scenarios
at a time (big HLO modules have thousands of call-sites).
"""
import gzip, os, sys
sys.path.insert(0, "src")
from repro.core import CommAdvisor, ExecPlan, hlo, price

args = [a for a in sys.argv[1:] if not a.startswith("--")]
do_sweep = "--sweep" in sys.argv
backend = "numpy"
chunk = None
for a in sys.argv[1:]:
    if a.startswith("--backend="):
        backend = a.split("=", 1)[1]
    elif a.startswith("--chunk="):
        chunk = int(a.split("=", 1)[1])
try:
    # ExecPlan.parse is the single source of backend validation — the
    # registry error lists what IS available (plugins included).
    plan = ExecPlan.parse(backend, chunk_scenarios=chunk)
except ValueError as e:
    sys.exit(f"error: {e}\n"
             "usage: top_collectives.py HLO.gz [N] [--sweep] "
             "[--backend=SPEC] [--chunk=K]")
if not args:
    sys.exit("error: missing HLO input\n"
             "usage: top_collectives.py HLO.gz [N] [--sweep] "
             "[--backend=SPEC] [--chunk=K]")
path = args[0]
n = int(args[1]) if len(args) > 1 else 12
if not os.path.isfile(path):
    sys.exit(f"error: HLO input not found: {path}")
text = gzip.open(path, "rt").read()
ops = hlo.parse_collectives(text)
ops.sort(key=lambda o: -o.total_wire_bytes)
total = sum(o.total_wire_bytes for o in ops)
print(f"total wire: {total/1e9:.1f} GB over {len(ops)} sites")
for o in ops[:n]:
    print(f"  {o.total_wire_bytes/1e9:8.1f} GB  {o.kind:18s} g={o.group_size:<3} "
          f"x{o.multiplier:<6.0f} {o.result_bytes/1e6:8.1f} MB/op  "
          f"{o.name[:28]:28s} in {o.computation[:44]}")

if do_sweep:
    advisor = CommAdvisor()
    res = price(text, advisor.default_grid(), plan=plan, advisor=advisor)
    frac_free = res.beneficial_mask().mean(axis=0)
    mean_gain = res.gain_ns.mean(axis=0)
    print(f"\nscenario sweep: {len(res.grid)} points, backend={plan.backend} "
          f"(cxl_lat x atomic at 0.5x..3x of the TPU preset)")
    order = sorted(range(len(res.call_ids)), key=lambda j: -mean_gain[j])
    for j in order[:n]:
        verdict = ("always-free" if frac_free[j] == 1.0 else
                   "never-free" if frac_free[j] == 0.0 else
                   f"free in {100 * frac_free[j]:3.0f}%")
        print(f"  {mean_gain[j]/1e3:10.1f} us mean gain  {verdict:14s} "
              f"{res.call_ids[j][:64]}")
