"""Record the scoped TPU trace that ``tests/test_trace_layers.py`` reads.

    python3 bench/tools/record_scoped_trace.py <out_dir>

The tiny net of ``tests/tiny_arch.py`` (batch 4, ``eyeriss_multinode``)
is solved, lowered and run through ``network_runner(backend="compiled",
keep="boundary")``: warmed up, then five calls under the profiler with
the program's tracer mirrored into it (``obs.trace.Tracer(profiler=
True)``), each call inside a ``bench.forward`` span and the whole inside
``bench.window``.  Written to ``<out_dir>``: ``scoped.xplane.pb``, and
``scoped.op_layers.json`` with ``FusedNetwork.op_layers("boundary")``
and each layer's kind.  The reduction (``trace_layers.reduce_scoped``)
is printed.
"""
from __future__ import annotations

import json
import os
import shutil
import sys
import tempfile

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(BENCH, "tests"), os.path.join(ROOT, "src")):
    sys.path.insert(0, p)

CALLS = 5


def main(out_dir: str) -> int:
    import jax
    import tiny_arch
    import trace_layers
    import trace_reduce
    from repro.core.solver import solve
    from repro.hw.presets import PRESETS
    from repro.lower import lower_network, make_network_inputs
    from repro.lower import network_runner
    from repro.lower.fuse import fused_runner
    from repro.obs import trace
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("no TPU")
    graph = tiny_arch.program_graph(4)
    hw = PRESETS["eyeriss_multinode"]()
    nplan = lower_network(solve(graph, hw), graph, hw)
    run = network_runner(nplan, make_network_inputs(nplan, seed=0),
                         backend="compiled", keep="boundary")
    run()
    run()
    op_layers = fused_runner(nplan).op_layers("boundary")
    kinds = {l.name: l.kind for l in graph.layers}
    tmp = tempfile.mkdtemp(prefix="scoped_")
    trace.enable(trace.Tracer(profiler=True))
    try:
        jax.profiler.start_trace(tmp)
        with jax.profiler.TraceAnnotation("bench.window"):
            for _ in range(CALLS):
                with jax.profiler.TraceAnnotation("bench.forward"):
                    run()
        jax.profiler.stop_trace()
    finally:
        trace.disable()
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, "scoped.xplane.pb")
    shutil.copy(trace_reduce.find_xplane(tmp), path)
    shutil.rmtree(tmp, ignore_errors=True)
    with open(os.path.join(out_dir, "scoped.op_layers.json"), "w") as f:
        json.dump({"calls": CALLS, "op_layers": op_layers, "kinds": kinds},
                  f, indent=1, sort_keys=True)
    print(path, os.path.getsize(path))
    print(json.dumps(trace_layers.reduce_scoped(path, op_layers, kinds)))
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1]))
