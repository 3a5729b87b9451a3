"""Benchmark harness: one module per paper table/figure + kernel micro-bench
+ the roofline report (reads dry-run artifacts if present).

    PYTHONPATH=src python -m benchmarks.run [--full]

Prints ``name,metric,value`` CSV lines; artifacts (JSON + plots) land in
benchmarks/results/.
"""
import argparse
import sys
import time
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale sweeps (slow); default is quick mode")
    args = ap.parse_args()
    quick = not args.full

    from repro.launch.compile_cache import enable_compile_cache

    from . import (fig1_convergence, fig2_scaling, fig3_sigma, kernel_bench,
                   table1_sigma)
    enable_compile_cache()

    failures = 0
    for name, mod in [("table1", table1_sigma), ("fig1", fig1_convergence),
                      ("fig2", fig2_scaling), ("fig3", fig3_sigma),
                      ("kernel", kernel_bench)]:
        t0 = time.time()
        print(f"==== {name} ====", flush=True)
        try:
            mod.run(quick=quick)
            print(f"{name},wall_s,{time.time() - t0:.1f}")
        except Exception as e:
            failures += 1
            print(f"{name},FAILED,{e}")
            traceback.print_exc()

    # roofline summary (requires dry-run artifacts)
    try:
        from . import roofline
        import pathlib
        for d in ("dryrun_opt", "dryrun"):
            p = roofline.DEFAULT_DIR.parent / d
            if p.exists() and list(p.glob("*.json")):
                recs, skips, fails = roofline.load_records(p)
                rows = [roofline.analyze(r) for r in recs]
                fracs = [r["roofline_fraction"] for r in rows
                         if r["roofline_fraction"]]
                print(f"roofline,{d},cells={len(rows)},skips={len(skips)},"
                      f"median_frac={sorted(fracs)[len(fracs)//2]:.4f}")
    except Exception as e:
        print(f"roofline,summary_skipped,{e}")

    print(f"done,failures,{failures}")
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
