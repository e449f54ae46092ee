"""Reduce a JAX profiler trace (``.xplane.pb``) to device busy and idle time.

    python bench/trace_reduce.py <file.xplane.pb | directory>

Only the harness's own host spans and the device's operation events are
read:

* the **window** is the host span named ``bench.window`` (the measured
  loop); without one, the whole span of the device's operations;
* **busy** is the union of the intervals of the events on each device
  plane's ``XLA Ops`` line, clipped to the window and averaged over the
  device planes; idle is the window less busy;
* **top ops** sums each operation's device time by the name the trace
  gives it (the HLO instruction, shown without layouts and attributes);
* **op classes** sums it by the instruction's name less its number
  (``slice``, ``convolution_add_fusion``, ``fusion``);
* **idle gaps** are the stretches of the window in which no operation
  ran, each named by the innermost ``bench.*`` host span that covers the
  middle of the gap (``host`` where none does).

A trace with no device plane reduces to nothing (``None``): the harness
then reports no device metric, never a 0.

The trace's device and host clocks are not quite one: on a TPU v5 lite
the device's events were stamped about a millisecond before the host
span that dispatched them (``tests/data/small.xplane.pb``).  Busy time
over a window of seconds does not notice; the label of an idle gap
shorter than a few milliseconds may name the span next to it.
"""
from __future__ import annotations

import glob
import json
import os
import re
import sys
from typing import Dict, List, Optional, Tuple

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."

Interval = Tuple[float, float]

_LAYOUT = re.compile(r"\{[^{}]*\}")
_ATTRS = re.compile(r"\),\s*[\w.-]+=.*$")
_CLASS = re.compile(r"^%?([A-Za-z_-]+?)(?:\.\d+)?(?:\s*=|$)")


def short_name(name: str, limit: int = 160) -> str:
    """``%slice.5 = f32[64,64,56,56] slice(f32[64,64,113,113] %pad.4)``
    from the trace's full HLO text of that instruction."""
    prev = None
    while prev != name:
        prev, name = name, _LAYOUT.sub("", name)
    return _ATTRS.sub(")", name)[:limit]


def op_class(name: str) -> str:
    m = _CLASS.match(name)
    return m.group(1) if m else name.split(" ")[0]


def find_xplane(path: str) -> str:
    """The newest ``.xplane.pb`` under ``path`` (or ``path`` itself)."""
    if os.path.isfile(path):
        return path
    found = glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return max(found, key=os.path.getmtime)


def union(intervals: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals: List[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def _events(plane, line_name: Optional[str] = None):
    for line in plane.lines:
        if line_name is None or line.name == line_name:
            for ev in line.events:
                yield line.name, ev


def read_planes(path: str):
    """(device op events per device plane, host ``bench.*`` spans), times
    in ns on the trace's common clock."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(find_xplane(path))
    devices: Dict[str, List[Tuple[str, float, float]]] = {}
    spans: List[Tuple[str, float, float]] = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            devices[plane.name] = [
                (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                for _, ev in _events(plane, OPS_LINE)]
        elif plane.name.startswith("/host:"):
            spans += [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                      for _, ev in _events(plane)
                      if ev.name.startswith(SPAN_PREFIX)]
    return devices, spans


def reduce(devices: Dict[str, List[Tuple[str, float, float]]],
           spans: List[Tuple[str, float, float]],
           top: int = 10) -> Optional[Dict]:
    """The reduction described in the module docstring; ``None`` when
    no device plane holds an operation."""
    devices = {k: v for k, v in devices.items() if v}
    if not devices:
        return None
    windows = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if windows:
        lo, hi = windows[0]
    else:
        lo = min(s for evs in devices.values() for _, s, _ in evs)
        hi = max(e for evs in devices.values() for _, _, e in evs)
    window_ns = hi - lo
    busy_ns, op_ns, gaps = [], {}, []
    inner = [sp for sp in spans if sp[0] != WINDOW_SPAN]
    for evs in devices.values():
        busy = union(clip([(s, e) for _, s, e in evs], lo, hi))
        busy_ns.append(sum(e - s for s, e in busy))
        for name, s, e in evs:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                op_ns[name] = op_ns.get(name, 0.0) + d
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                gaps.append((_label(inner, (s + e) / 2), e - s))
    n = len(devices)
    classes: Dict[str, float] = {}
    for k, v in op_ns.items():
        classes[op_class(k)] = classes.get(op_class(k), 0.0) + v
    ops = sorted(op_ns.items(), key=lambda kv: -kv[1])[:top]
    gaps.sort(key=lambda g: -g[1])
    busy_s = sum(busy_ns) / n / 1e9
    return {"devices": n,
            "window_s": window_ns / 1e9,
            "busy_s": busy_s,
            "idle_share": 1.0 - busy_s / (window_ns / 1e9),
            "device_ops": [[short_name(k), v / n / 1e9] for k, v in ops],
            "op_classes": [[k, v / n / 1e9] for k, v in sorted(
                classes.items(), key=lambda kv: -kv[1])[:top]],
            "idle_gaps": [[k, v / 1e9] for k, v in gaps[:top]]}


def _label(spans: List[Tuple[str, float, float]], t: float) -> str:
    covering = [(e - s, n) for n, s, e in spans if s <= t <= e]
    return min(covering)[1] if covering else "host"


def reduce_file(path: str, top: int = 10) -> Optional[Dict]:
    return reduce(*read_planes(path), top=top)


if __name__ == "__main__":
    print(json.dumps(reduce_file(sys.argv[1]), indent=1))
