"""Record the small TPU trace that ``tests/test_trace_reduce.py`` reads.

    python3 bench/tools/record_trace.py <out_dir>

Three calls of a jitted 4096x4096 matmul chain under the profiler, each
inside a ``bench.forward`` host span, the whole inside ``bench.window``,
with a 20 ms host sleep (``bench.next_input``) before each call so that
the device idles in known gaps.  The
newest ``.xplane.pb`` under ``<out_dir>`` is the recording; a summary of
its planes and lines is printed, so a reader can see how the device's
operations are named.
"""
from __future__ import annotations

import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)


def main(out_dir: str) -> int:
    import jax
    import jax.numpy as jnp
    import trace_reduce
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("no TPU")
    f = jax.jit(lambda x: jnp.tanh(x @ x) @ x)
    x = jnp.full((4096, 4096), 1.0 / 4096, jnp.float32)
    f(x).block_until_ready()
    jax.profiler.start_trace(out_dir)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.next_input"):
                time.sleep(0.020)
            with jax.profiler.TraceAnnotation("bench.forward"):
                f(x).block_until_ready()
    jax.profiler.stop_trace()
    path = trace_reduce.find_xplane(out_dir)
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    for plane in data.planes:
        lines = [(ln.name, sum(1 for _ in ln.events)) for ln in plane.lines]
        print("plane", plane.name, lines)
        for ln in plane.lines:
            evs = list(ln.events)[:4]
            print("   ", ln.name, [(e.name, e.start_ns, e.duration_ns)
                                   for e in evs])
    print(path, os.path.getsize(path))
    print(json.dumps(trace_reduce.reduce_file(path)))
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1]))
