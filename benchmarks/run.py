"""Benchmark harness entry point — one function per paper table/figure.

Output: ``name,us_per_call,derived`` CSV rows (benchmarks/common.emit).

    PYTHONPATH=src python -m benchmarks.run [--only table5,fig5]
"""
from __future__ import annotations

import argparse
import sys
import traceback

from repro.runtime.compile_cache import enable_compile_cache


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, help="comma-separated subset")
    args = ap.parse_args()

    from benchmarks import (
        fig5_hit_miss,
        fig6_energy,
        kernel_micro,
        lm_roofline,
        table3_slice_size,
        table4_valid_pct,
        table5_runtime,
    )

    suites = {
        "table3": table3_slice_size.run,
        "table4": table4_valid_pct.run,
        "table5": table5_runtime.run,
        "fig5": fig5_hit_miss.run,
        "fig6": fig6_energy.run,
        "kernels": kernel_micro.run,
        "lm_roofline": lm_roofline.run,
    }
    selected = args.only.split(",") if args.only else list(suites)
    print("name,us_per_call,derived")
    failed = 0
    for name in selected:
        try:
            suites[name]()
        except Exception:
            failed += 1
            traceback.print_exc()
            print(f"{name}/ERROR,0,failed")
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    enable_compile_cache()
    main()
