#!/usr/bin/env python3
"""Record the small trace that test_trace_reduce.py reads: five runs of
one jitted program that has two of the program's named scopes
(`m3.decode` around a loop, `m3.temporal` around a matrix product),
each after 2 ms under an `m3:fetch` annotation and inside `m3:device`
holding `m3:kernel`, with 10 ms of sleep between them, all inside the
harness's `bench:window`.  Run on the chip; prints what the planes
hold.

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
    def small_program(x, rounds):
        with jax.named_scope("m3.decode"):
            # a bound read on the device keeps this a loop
            _, y = jax.lax.while_loop(
                lambda c: c[0] < rounds,
                lambda c: (c[0] + 1, jnp.tanh(c[1] * 1.5 + 0.25)), (0, x))
        with jax.named_scope("m3.temporal"):
            return (y @ x).sum()

    x = jnp.ones((1024, 1024), dtype=jnp.float32)
    rounds = jnp.int32(6)
    small_program(x, rounds).block_until_ready()
    shutil.rmtree(out_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(out_dir, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench:window"):
        for _ in range(5):
            with jax.profiler.TraceAnnotation("m3:fetch"):
                time.sleep(0.002)
            with jax.profiler.TraceAnnotation("m3:device"):
                with jax.profiler.TraceAnnotation("m3:kernel"):
                    small_program(x, rounds).block_until_ready()
            time.sleep(0.01)
    jax.profiler.stop_trace()
    path = trace_reduce.find_xplane(out_dir)
    data = jax.profiler.ProfileData.from_file(path)
    for plane in data.planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            events = list(line.events)
            print("  LINE", line.name, len(events))
            for ev in events[:16]:
                print("     ", ev.name[:120], ev.start_ns, ev.duration_ns)
    print(json.dumps(trace_reduce._op_scopes(path), indent=1))
    print(json.dumps(trace_reduce.reduce(path), indent=1))
    print("xplane", path, pathlib.Path(path).stat().st_size, "bytes")


if __name__ == "__main__":
    main(sys.argv[1])
