"""Benchmark driver: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows plus a validation summary
(every check compares our result against the published value).

  PYTHONPATH=src python -m benchmarks.run [--fast]
"""

from __future__ import annotations

import argparse
import sys
import time

from benchmarks import ablations, kernel_bench, paper_tables


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="skip the slow end-to-end TM training benches")
    args = ap.parse_args()

    benches = [
        ("table_i", paper_tables.table_i),
        ("table_ii", paper_tables.table_ii),
        ("table_iii", paper_tables.table_iii),
        ("table_iv", paper_tables.table_iv),
        ("fig5_programming", paper_tables.fig5_programming),
        ("fig6_timing", paper_tables.fig6_timing),
        ("fig7_variations", paper_tables.fig7_variations),
        ("fig8_pulse", paper_tables.fig8_pulse),
        ("fig9_topj", paper_tables.fig9_topj),
        ("kernels", kernel_bench.bench),
    ]
    benches += [("ablation_column_width", ablations.column_width_sweep)]
    if not args.fast:
        benches += [("tm_accuracy", paper_tables.tm_accuracy),
                    ("tm_image_accuracy", paper_tables.tm_image_accuracy),
                    ("ablation_coalesced", ablations.coalesced_vs_vanilla)]

    all_checks = []
    print("name,us_per_call,derived")
    for name, fn in benches:
        t0 = time.perf_counter()
        rows, checks = fn()
        us = (time.perf_counter() - t0) * 1e6
        print(f"{name},{us:.0f},rows={len(rows)}")
        for row in rows:
            print(f"{name}/{row[0]},,{','.join(str(v) for v in row[1:])}")
        all_checks.extend(checks)

    print("\n=== validation against published values ===")
    n_ok = 0
    for cname, ok, detail in all_checks:
        print(f"[{'PASS' if ok else 'FAIL'}] {cname}: {detail}")
        n_ok += bool(ok)
    print(f"{n_ok}/{len(all_checks)} checks passed")
    if n_ok != len(all_checks):
        sys.exit(1)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
