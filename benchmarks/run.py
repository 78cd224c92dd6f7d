"""Benchmark harness entrypoint: one benchmark per paper table.

  PYTHONPATH=src python -m benchmarks.run            # all
  PYTHONPATH=src python -m benchmarks.run dialect    # Tables II/III
  PYTHONPATH=src python -m benchmarks.run tablev     # Table V kernels
  PYTHONPATH=src python -m benchmarks.run roofline   # §Roofline table
"""
from __future__ import annotations

import json
import os
import sys

from benchmarks import dialect_audit, roofline_table, tablev
from repro.launch.compile_cache import enable_compile_cache


def main() -> None:
    enable_compile_cache()
    which = sys.argv[1] if len(sys.argv) > 1 else "all"
    results = {}
    if which in ("all", "dialect"):
        results["dialect_audit"] = dialect_audit.run()
        print()
    if which in ("all", "tablev"):
        results["tablev"] = tablev.run()
        print()
    if which in ("all", "roofline"):
        results["roofline"] = roofline_table.run()
    os.makedirs("results/bench", exist_ok=True)
    with open("results/bench/summary.json", "w") as f:
        json.dump(results, f, indent=1, default=str)
    print("\n[benchmarks] wrote results/bench/summary.json")


if __name__ == "__main__":
    main()
