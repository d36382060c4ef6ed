"""Benchmark harness: one benchmark per paper table/figure (+ the roofline).

``PYTHONPATH=src python -m benchmarks.run [--full]``

Defaults to the quick profile (CPU-friendly); --full runs the paper-sized
sweeps.  Output: CSV blocks per benchmark, identical schema either way.
"""

from __future__ import annotations

import argparse
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default=None,
                    help="table1|fig3|fig6|fig4|roofline")
    args = ap.parse_args()
    quick = not args.full
    t0 = time.time()

    def banner(name):
        print(f"\n===== {name} =====", flush=True)

    ok = True
    if args.only in (None, "table1"):
        banner("Table 1: LL parity einsum vs naive + EM improvement")
        from benchmarks import bench_table1

        ok &= bool(bench_table1.main(quick=quick))
    if args.only in (None, "fig3"):
        banner("Fig 3: train time / peak memory vs K, D, R")
        from benchmarks import bench_fig3

        bench_fig3.main(quick=quick)
    if args.only in (None, "fig6"):
        banner("Fig 6: inference time vs K, D, R")
        from benchmarks import bench_fig6

        bench_fig6.main(quick=quick)
    if args.only in (None, "fig4"):
        banner("Fig 4: generative image model + inpainting")
        from benchmarks import bench_fig4

        bench_fig4.main(quick=quick)
    if args.only in (None, "roofline"):
        banner("Roofline table (from dry-run artifacts, 16x16 mesh)")
        import os

        from benchmarks import roofline

        if os.path.isdir("artifacts/dryrun"):
            rows = roofline.build_table("artifacts/dryrun", "16x16")
            print(roofline.to_markdown(rows))
        else:
            print("no artifacts/dryrun: run repro.launch.dryrun first")
    print(f"\n# benchmarks done in {time.time()-t0:.1f}s; all-ok={ok}")
    if not ok:
        sys.exit(1)


if __name__ == "__main__":
    from repro.launch import compile_cache

    compile_cache.enable()
    main()
