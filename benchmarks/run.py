"""Benchmark harness entrypoint: one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run           # all
    PYTHONPATH=src python -m benchmarks.run fig10     # one

Prints ``name,us_per_call,derived`` CSV rows.
"""
from __future__ import annotations

import sys
import time

from repro.runtime.compile_cache import enable_compile_cache

MODULES = ["fig9_endurance", "table4_offload", "fig10_overhead",
           "fig11_rok", "io_backends", "spool_datapath",
           "cache_manager", "roofline"]


def main() -> None:
    enable_compile_cache()
    want = sys.argv[1:] or MODULES
    for name in want:
        mod = name if name in MODULES else next(
            (m for m in MODULES if m.startswith(name)), None)
        if mod is None:
            raise SystemExit(f"unknown benchmark {name!r}; "
                             f"known: {MODULES}")
        print(f"# === benchmarks.{mod} ===", flush=True)
        t0 = time.time()
        __import__(f"benchmarks.{mod}", fromlist=["main"]).main()
        print(f"# {mod} took {time.time()-t0:.1f}s", flush=True)


if __name__ == "__main__":
    main()
