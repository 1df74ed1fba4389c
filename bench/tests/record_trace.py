#!/usr/bin/env python3
"""Record the small profiler trace that test_trace.py reads, on a chip.

    python bench/tests/record_trace.py <out_dir>

Three steps of a jitted matmul chain, each followed by 50 ms of host
sleep inside a `host.sleep` annotation, between the harness's
`bench.trace_start` and `bench.trace_end` marks. Prints the planes and
lines of the trace, and the reduction of bench/trace.py.
"""
import json
import shutil
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path[:] = [p for p in sys.path
               if Path(p or ".").resolve() not in (BENCH, BENCH / "tests")]
sys.path[:0] = [str(BENCH.parent), str(BENCH.parent / "src")]

SLEEP_S = 0.05
STEPS = 3


def main() -> int:
    import jax
    import jax.numpy as jnp

    from bench import trace

    out = Path(sys.argv[1])
    tmp = out / "_profile"
    x = jnp.ones((2048, 2048), jnp.bfloat16)
    f = jax.jit(lambda a: jnp.tanh(a @ a) @ a)
    f(x).block_until_ready()
    trace.start(str(tmp))
    with jax.profiler.TraceAnnotation(trace.START_MARK):
        pass
    for _ in range(STEPS):
        f(x).block_until_ready()
        with jax.profiler.TraceAnnotation("host.sleep"):
            time.sleep(SLEEP_S)
    with jax.profiler.TraceAnnotation(trace.END_MARK):
        pass
    jax.profiler.stop_trace()
    path = trace.find_xplane(str(tmp))
    pd = trace.load(path)
    for plane in pd.planes:
        lines = list(plane.lines)
        print("plane", plane.name, [(l.name, len(list(l.events)))
                                    for l in lines])
        for l in lines:
            for ev in list(l.events)[:3]:
                print("   ", l.name, "|", ev.name, ev.start_ns,
                      ev.duration_ns)
    print(json.dumps(trace.reduce(pd)))
    shutil.copy(path, out / "three_steps.xplane.pb")
    shutil.rmtree(tmp)
    return 0


if __name__ == "__main__":
    sys.exit(main())
