"""Benchmark driver: one harness per paper table/figure.

Prints ``name,us_per_call,derived`` CSV lines.  The roofline analysis
(§Roofline) additionally reads experiments/dryrun/*.json — run
``python -m repro.launch.dryrun --all --mesh both`` first to refresh it.
"""
from __future__ import annotations

import sys
import traceback

from benchmarks import (fig3_abn_accuracy, fig6_split_dpl, fig8_settling,
                        fig10_20_nonidealities, fig13_adc, fig17_macro,
                        fig22_efficiency, kernel_bench, table1)
from repro.launch.compile_cache import enable_compile_cache


def main() -> None:
    enable_compile_cache()
    suites = [
        ("fig6_split_dpl", fig6_split_dpl.main),
        ("fig8_settling", fig8_settling.main),
        ("fig10_20_nonidealities", fig10_20_nonidealities.main),
        ("fig13_adc", fig13_adc.main),
        ("fig17_macro", fig17_macro.main),
        ("fig22_efficiency", fig22_efficiency.main),
        ("table1", table1.main),
        ("kernel_bench", kernel_bench.main),
        ("fig3_abn_accuracy", fig3_abn_accuracy.main),   # slowest last
    ]
    failures = 0
    for name, fn in suites:
        try:
            fn()
        except Exception:
            failures += 1
            print(f"{name},0,FAILED", flush=True)
            traceback.print_exc()
    # roofline table if dry-run artifacts exist
    try:
        import glob
        if glob.glob("experiments/dryrun/*.json"):
            from benchmarks import roofline
            rows = []
            for cell in roofline.load_cells():
                r = roofline.roofline_row(cell)
                if r is not None:
                    rows.append(r)
            fr = [r["roofline_frac"] for r in rows]
            print(f"roofline_cells,0,n{len(rows)}_fracmin{min(fr):.3f}"
                  f"_fracmax{max(fr):.3f}")
    except Exception:
        print("roofline,0,FAILED")
        traceback.print_exc()
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
