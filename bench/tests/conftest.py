"""The benchmark's own tests: ``python -m pytest bench/tests``.

They run on the CPU at small sizes: the harness's look for a chip is
skipped by calling ``run.run_cell`` directly.
"""
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
