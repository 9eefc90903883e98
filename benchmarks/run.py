"""Benchmark aggregator: one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run            # all
    PYTHONPATH=src python -m benchmarks.run fig04 fig18

Prints ``name,us_per_call,derived`` CSV rows (benchmarks/common.emit).
"""

import sys
import time
import traceback

MODULES = [
    "fig01_breakdown",
    "fig04_scheduling",
    "fig06_heatmap",
    "fig09_operator_scaling",
    "fig11_fused_prep",
    "fig13_library",
    "fig14_dispatch_overhead",
    "fig16_multipod",
    "fig18_guideline_eval",
    "roofline_report",
]


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    sel = [a for a in sys.argv[1:] if not a.startswith("-")]
    print("name,us_per_call,derived")
    failures = []
    for name in MODULES:
        if sel and not any(s in name for s in sel):
            continue
        mod = __import__(f"benchmarks.{name}", fromlist=["main"])
        t0 = time.time()
        try:
            mod.main()
        except Exception as e:  # noqa: BLE001 - report, keep going
            failures.append((name, repr(e)))
            traceback.print_exc(limit=3)
        print(f"# {name} done in {time.time() - t0:.1f}s", file=sys.stderr)
    if failures:
        print(f"# FAILURES: {failures}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
