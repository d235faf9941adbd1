"""Benchmark harness — one module per paper table/figure.
Prints ``name,us_per_call,derived`` CSV."""
from __future__ import annotations

import sys
import traceback


def main() -> None:
    from benchmarks import (bench_activation_memory, bench_kernels,
                            bench_mfu_table1, bench_pipeline_bubble,
                            bench_roofline, bench_serve_throughput,
                            bench_table2_strategies, bench_table3_search)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    modules = [
        ("table1_mfu", bench_mfu_table1),
        ("table2_strategies", bench_table2_strategies),
        ("table3_search", bench_table3_search),
        ("fig5_pipeline_bubble", bench_pipeline_bubble),
        ("korthikanti_activation_memory", bench_activation_memory),
        ("kernels", bench_kernels),
        ("roofline", bench_roofline),
        ("serve_throughput", bench_serve_throughput),
    ]
    print("name,us_per_call,derived")
    failures = 0
    for name, mod in modules:
        try:
            for row in mod.run():
                derived = str(row["derived"]).replace(",", ";")
                print(f"{row['name']},{row['us_per_call']},{derived}")
        except Exception:
            traceback.print_exc()
            failures += 1
            print(f"{name},0,FAILED")
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
