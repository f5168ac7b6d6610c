"""Benchmark harness: one section per paper table/figure, plus the roofline
and advisor reports for the TPU adaptation.

Usage:  PYTHONPATH=src python -m benchmarks.run [--quick]
"""
from __future__ import annotations

import argparse
import time

from . import (advisor, fig5_stencil, fig7_multinode, fig8_breakdown,
               fig9_hpcg, fig10_hpcg_breakdown, roofline)

SECTIONS = [
    ("Fig5: stencil reference vs model", fig5_stencil.run),
    ("Fig7: multi-node CXL.mem prediction (1.37x/1.59x claims)",
     fig7_multinode.run),
    ("Fig8: stencil overhead breakdown", fig8_breakdown.run),
    ("Fig9: HPCG reference vs model", fig9_hpcg.run),
    ("Fig10: HPCG overhead breakdown", fig10_hpcg_breakdown.run),
]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    args = ap.parse_args(argv)

    for title, fn in SECTIONS:
        print(f"\n{'='*72}\n== {title}\n{'='*72}")
        t0 = time.time()
        fn(quick=args.quick)
        print(f"-- section done in {time.time()-t0:.1f}s")

    print(f"\n{'='*72}\n== Roofline (from dry-run artifacts, single-pod "
          f"16x16)\n{'='*72}")
    roofline.run("16x16")
    print(f"\n{'='*72}\n== Roofline (multi-pod 2x16x16)\n{'='*72}")
    roofline.run("2x16x16")

    print(f"\n{'='*72}\n== CommAdvisor: paper model per HLO collective\n"
          f"{'='*72}")
    advisor.run("16x16")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
