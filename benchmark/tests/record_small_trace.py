#!/usr/bin/env python3
"""Record the small trace that test_trace_reduce.py reads: five runs of
one jitted program with sleeps between them, under the harness's
`bench:` annotations.  Run on the chip; prints what the planes hold.

    python benchmark/tests/record_small_trace.py <out_dir>
"""

from __future__ import annotations

import json
import pathlib
import shutil
import sys
import time

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))


def main(out_dir: str) -> None:
    import jax
    import jax.numpy as jnp

    from harness import trace_reduce

    @jax.jit
    def small_program(x):
        return jnp.tanh(x @ x).sum()

    x = jnp.ones((1024, 1024), dtype=jnp.float32)
    small_program(x).block_until_ready()
    shutil.rmtree(out_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(out_dir, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench:window"):
        for _ in range(5):
            with jax.profiler.TraceAnnotation("bench:awaiting_reply"):
                small_program(x).block_until_ready()
            time.sleep(0.01)
    jax.profiler.stop_trace()
    path = trace_reduce.find_xplane(out_dir)
    data = jax.profiler.ProfileData.from_file(path)
    for plane in data.planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            events = list(line.events)
            print("  LINE", line.name, len(events))
            for ev in events[:4]:
                print("     ", ev.name, ev.start_ns, ev.duration_ns,
                      dict(ev.stats))
    print(json.dumps(trace_reduce.reduce(path), indent=1))
    print("xplane", path, pathlib.Path(path).stat().st_size, "bytes")


if __name__ == "__main__":
    main(sys.argv[1])
