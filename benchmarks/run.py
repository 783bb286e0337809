"""Benchmark runner — one section per paper table/figure.
Prints ``name,us_per_call,derived`` CSV rows. Every section runs even
when an earlier one raised; the exit status is 1 if any section raised.

    PYTHONPATH=src python -m benchmarks.run [--only fig8,fig13] [--fast]
"""

from __future__ import annotations

import argparse
import sys
import time

from benchmarks.common import Bench
from repro.compile_cache import enable_compile_cache


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="")
    ap.add_argument("--fast", action="store_true",
                    help="smaller sweeps for CI")
    args = ap.parse_args()

    from benchmarks import (fig8_coldstart, fig9_breakdown, fig10_cv,
                            fig11_slo, fig12_apps, fig13_scaledown,
                            fig14_scaleup, fig15_brownfield,
                            roofline_report, table1_warm)

    sections = {
        "table1": table1_warm.run,
        "fig8": fig8_coldstart.run,
        "fig9": fig9_breakdown.run,
        "fig10": (lambda b: fig10_cv.run(b, cvs=(8.0,), rates=(0.6,)))
        if args.fast else fig10_cv.run,
        "fig11": (lambda b: fig11_slo.run(b, scales=(1.0,)))
        if args.fast else fig11_slo.run,
        "fig12": fig12_apps.run,
        "fig13": fig13_scaledown.run,
        "fig14": (lambda b: fig14_scaleup.run(b, loads=(64,)))
        if args.fast else fig14_scaleup.run,
        "fig15": fig15_brownfield.run,
        "roofline": roofline_report.run,
    }
    only = [s for s in args.only.split(",") if s]
    bench = Bench()
    failed = []
    for name, fn in sections.items():
        if only and name not in only:
            continue
        t0 = time.time()
        try:
            fn(bench)
        except Exception as e:  # noqa: BLE001
            bench.add(f"{name}/ERROR", 0.0, repr(e)[:120])
            failed.append(name)
            import traceback
            traceback.print_exc(file=sys.stderr)
        print(f"# section {name} done in {time.time()-t0:.1f}s",
              file=sys.stderr)
    bench.emit()
    if failed:
        sys.exit(f"sections failed: {','.join(failed)}")


if __name__ == "__main__":
    main()
