"""Benchmark driver: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.

    PYTHONPATH=src python -m benchmarks.run            # all
    PYTHONPATH=src python -m benchmarks.run table4     # one
"""
from __future__ import annotations

import sys
import time
import traceback

from benchmarks import (fig7_inference_time, fig8_framework, fig11_dxenos,
                        roofline, table2_auto_time, table4_operators)
from repro.launch.compile_cache import enable_compile_cache

SUITES = {
    "fig7": fig7_inference_time.run,
    "fig8": fig8_framework.run,
    "table2": table2_auto_time.run,
    "table4": table4_operators.run,
    "fig11": fig11_dxenos.run,
    "roofline": roofline.run,
}


def main() -> None:
    wanted = sys.argv[1:] or list(SUITES)
    print("name,us_per_call,derived")
    failures = []
    for name in wanted:
        t0 = time.time()
        try:
            SUITES[name]()
        except Exception:  # noqa: BLE001
            traceback.print_exc()
            failures.append(name)
        print(f"# suite {name} finished in {time.time() - t0:.1f}s")
    if failures:
        print(f"# FAILED suites: {failures}")
        raise SystemExit(1)


if __name__ == "__main__":
    enable_compile_cache()
    main()
