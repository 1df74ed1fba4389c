#!/usr/bin/env python3
"""Run one cell of the chip benchmark.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

The cell's files are found by name (see `bench/harness.py`). The last
line of standard output is one JSON object: `correct`, `attempted`,
`failed`, `metrics`, `device`, with `--trace 1` also `breakdown`, and
last `compared`, each compared number beside its limit; the same
numbers are the last lines of standard error. `--trace 0` reports the
cell's end-to-end metrics, `--trace 1` its per-layer metrics.

Exits non-zero with no result line when JAX finds no TPU, or fewer
chips than the cell asks for.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

T0 = time.perf_counter()
BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
# bench/ itself must not shadow the standard library (bench/trace.py)
sys.path[:] = [p for p in sys.path if Path(p or ".").resolve() != BENCH]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def compile_cache_dir() -> str:
    """JAX's persistent compilation cache: where the environment says,
    else at a fixed path inside the checkout."""
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or str(ROOT / ".jax_cache"))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    from bench import harness
    wl = harness.load_workload(args.workload)

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < wl["chips"]:
        print(f"bench: needs {wl['chips']} TPU chip(s); JAX found "
              f"{len(devs)} {devs[0].platform} device(s). Nothing was run.",
              file=sys.stderr)
        return 3
    jax.config.update("jax_compilation_cache_dir", compile_cache_dir())
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    result = harness.run_cell(args.workload, args.seed, args.seconds,
                              bool(args.trace), T0)
    for k, v in result["compared"].items():
        print(f"{k} {v['value']!r} limit {v['limit']!r}", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
