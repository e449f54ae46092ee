"""Join a profiler trace with the program's own layer scopes and spans.

    reduce_scoped(<file.xplane.pb | directory>, op_layers, kinds)

``trace_reduce.py`` sees the device's operations by HLO instruction and
the harness's ``bench.*`` spans.  A run that enables the program's tracer
with profiler mirroring (``obs.trace.Tracer(profiler=True)``) and asks
``FusedNetwork.op_layers()`` in set-up gives two more things to join:

* **device time per layer and per kind**: each ``XLA Ops`` event's time,
  clipped to the window, summed by the layer that ``op_layers`` maps its
  instruction to (``kinds`` maps a layer to ``conv``, ``pool``,
  ``eltwise`` or ``fc``); events outside every layer count as
  ``unattributed``;
* **the clock skew**: the k-th ``XLA Modules`` event (one a run of the
  executable) is paired with the k-th ``fuse.dispatch`` span and the
  k-th ``netexec.wait`` span.  The skew is the smallest shift of the
  device clock that puts every module start after its dispatch start;
  the slack is what is left before a module end, so shifted, would pass
  the end of its wait.  Where the counts differ there is no skew, and
  nothing below that needs it is reported;
* **idle by program span**: the window's idle intervals, after the
  shift, intersected with the union of each group of program spans on
  the thread that dispatches (``dispatch``: ``fuse.feed`` and
  ``fuse.dispatch``; ``wait``: ``netexec.wait``).  The true shift lies
  between the skew and the skew plus its slack; ``idle_by_span`` is
  taken at the skew, ``idle_by_span_late`` at the other end;
* **program gaps**: the longest idle gaps after the shift, each named by
  the innermost span (program or harness) at its middle.

The output is ``trace_reduce.reduce``'s, unchanged, with these keys
added.  Times are in ns on the trace's clock until the output, which is
in seconds (``clock_skew_ms`` in ms).
"""
from __future__ import annotations

import re
from typing import Dict, List, Mapping, Optional, Sequence, Tuple

import trace_reduce
from trace_reduce import Interval, clip, union

MODULES_LINE = "XLA Modules"
#: name prefixes of the program's spans (``obs.trace``) read from the
#: trace's host planes
PROGRAM_PREFIXES = ("netexec.", "fuse.", "host.", "lower.", "service.",
                    "store.")
DISPATCH = "fuse.dispatch"
WAIT = "netexec.wait"
IDLE_GROUPS = {"dispatch": ("fuse.feed", DISPATCH), "wait": (WAIT,)}
KINDS = ("conv", "pool", "eltwise", "fc")

Event = Tuple[str, float, float]
Span = Tuple[str, float, float, str]       # name, start, end, host line

_INSTR = re.compile(r"^%?([\w.-]+)")


def instruction(event_name: str) -> str:
    """``slice.567`` from the trace's ``%slice.567 = f32[...] slice(...)``."""
    m = _INSTR.match(event_name)
    return m.group(1) if m else event_name


def read_trace(path: str, prefixes: Sequence[str] = PROGRAM_PREFIXES):
    """(ops, modules, bench spans, program spans) of a trace: per device
    plane its ``XLA Ops`` and its ``XLA Modules`` events; the harness's
    ``bench.*`` spans; the program's spans with the host line (thread)
    they ran on."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(trace_reduce.find_xplane(path))
    ops: Dict[str, List[Event]] = {}
    modules: Dict[str, List[Event]] = {}
    bench: List[Event] = []
    program: List[Span] = []
    for plane in data.planes:
        if plane.name.startswith(trace_reduce.DEVICE_PREFIX):
            for line in plane.lines:
                if line.name in (trace_reduce.OPS_LINE, MODULES_LINE):
                    out = ops if line.name == trace_reduce.OPS_LINE \
                        else modules
                    out[plane.name] = [
                        (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                        for ev in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    iv = (ev.start_ns, ev.start_ns + ev.duration_ns)
                    if ev.name.startswith(trace_reduce.SPAN_PREFIX):
                        bench.append((ev.name, *iv))
                    elif ev.name.startswith(tuple(prefixes)):
                        program.append((ev.name, *iv, line.name))
    return ops, modules, bench, program


def window(ops: Mapping[str, List[Event]],
           bench: List[Event]) -> Interval:
    """The window as ``trace_reduce.reduce`` takes it: ``bench.window``,
    else the extent of the device's operations."""
    for name, s, e in bench:
        if name == trace_reduce.WINDOW_SPAN:
            return s, e
    return (min(s for evs in ops.values() for _, s, _ in evs),
            max(e for evs in ops.values() for _, _, e in evs))


def layer_time(ops: Mapping[str, List[Event]], op_layers: Mapping[str, str],
               lo: float, hi: float) -> Tuple[Dict[str, float],
                                              Dict[str, float]]:
    """({layer: ns}, {unattributed instruction: ns}) of the window,
    averaged over the device planes."""
    layers: Dict[str, float] = {}
    other: Dict[str, float] = {}
    for evs in ops.values():
        for name, s, e in evs:
            d = min(e, hi) - max(s, lo)
            if d <= 0:
                continue
            instr = instruction(name)
            layer = op_layers.get(instr)
            out, key = (layers, layer) if layer is not None \
                else (other, instr)
            out[key] = out.get(key, 0.0) + d / len(ops)
    return layers, other


def clock_skew(modules: Mapping[str, List[Event]],
               dispatch: List[Interval],
               wait: List[Interval]) -> Optional[Tuple[float, float, int]]:
    """(skew, slack, pairs) in ns, or None where the counts differ.

    The k-th module of each device plane runs the k-th dispatched call:
    skew = max(dispatch start - module start), the least shift of the
    device clock that keeps every module from starting before it was
    dispatched; slack = min(wait end - (module end + skew)), which a
    consistent pairing leaves at 0 or above."""
    dispatch, wait = sorted(dispatch), sorted(wait)
    if not dispatch or len(wait) != len(dispatch):
        return None
    pairs = []
    for evs in modules.values():
        mods = sorted((s, e) for _, s, e in evs)
        if len(mods) != len(dispatch):
            return None
        pairs += list(zip(mods, dispatch, wait))
    if not pairs:
        return None
    skew = max(d[0] - m[0] for m, d, _ in pairs)
    slack = min(w[1] - (m[1] + skew) for m, _, w in pairs)
    return skew, slack, len(dispatch)


def idle_intervals(evs: List[Event], shift: float, lo: float,
                   hi: float) -> List[Interval]:
    """The window's stretches with no operation, device times shifted."""
    busy = union(clip([(s + shift, e + shift) for _, s, e in evs], lo, hi))
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    return [(s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s]


def overlap(a: List[Interval], b: List[Interval]) -> float:
    """Length of the intersection of two sorted, disjoint interval lists."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        total += max(0.0, min(a[i][1], b[j][1]) - max(a[i][0], b[j][0]))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def idle_by_span(ops: Mapping[str, List[Event]], spans: List[Span],
                 shift: float, lo: float, hi: float,
                 groups: Mapping[str, Sequence[str]] = IDLE_GROUPS
                 ) -> Dict[str, float]:
    """{group: ns} the device idles while the host is in one of the
    group's spans, averaged over the device planes."""
    out = {g: 0.0 for g in groups}
    for evs in ops.values():
        idle = idle_intervals(evs, shift, lo, hi)
        for g, names in groups.items():
            host = union([(s, e) for n, s, e, _ in spans if n in names])
            out[g] += overlap(idle, host) / len(ops)
    return out


def _label(spans: List[Event], t: float) -> str:
    covering = [(e - s, n) for n, s, e in spans if s <= t <= e]
    return min(covering)[1] if covering else "host"


def program_gaps(ops: Mapping[str, List[Event]], spans: List[Event],
                 shift: float, lo: float, hi: float,
                 top: int = 10) -> List[List]:
    """The ``top`` longest idle gaps after the shift, [label, ns] each."""
    gaps = [(_label(spans, (s + e) / 2), e - s)
            for evs in ops.values()
            for s, e in idle_intervals(evs, shift, lo, hi)]
    gaps.sort(key=lambda g: -g[1])
    return [list(g) for g in gaps[:top]]


def reduce_scoped(path: str, op_layers: Mapping[str, str],
                  kinds: Mapping[str, str], top: int = 10
                  ) -> Optional[Dict]:
    """``trace_reduce.reduce`` of the trace, with the keys the module
    docstring lists: ``layers`` ({layer: s}), ``layer_time``,
    ``clock_skew_ms``, ``idle_by_span``, ``idle_by_span_late`` and
    ``program_gaps``."""
    ops, modules, bench, program = read_trace(path)
    out = trace_reduce.reduce(ops, bench, top=top)
    if out is None:
        return None
    ops = {k: v for k, v in ops.items() if v}
    lo, hi = window(ops, bench)
    layers, other = layer_time(ops, op_layers, lo, hi)
    by_kind = {k: 0.0 for k in KINDS}
    for layer, ns in layers.items():
        kind = kinds.get(layer, "unattributed")
        by_kind[kind] = by_kind.get(kind, 0.0) + ns
    by_kind["unattributed"] = by_kind.get("unattributed", 0.0) \
        + sum(other.values())
    ranked = sorted(layers.items(), key=lambda kv: -kv[1])
    out["layers"] = {k: v / 1e9 for k, v in layers.items()}
    out["layer_time"] = {
        "by_kind": {k: v / 1e9 for k, v in by_kind.items()},
        "top": [[k, v / 1e9] for k, v in ranked[:top]],
        "busy_share": sum(layers.values()) / 1e9 / out["busy_s"]
        if out["busy_s"] > 0 else None,
        "unattributed_ops": [[k, v / 1e9] for k, v in sorted(
            other.items(), key=lambda kv: -kv[1])[:5]]}

    threads = {line for n, _, _, line in program if n == DISPATCH}
    mine = [sp for sp in program if sp[3] in threads]
    skew = clock_skew(
        {k: v for k, v in modules.items() if k in ops},
        [(s, e) for n, s, e, _ in mine if n == DISPATCH],
        [(s, e) for n, s, e, _ in mine if n == WAIT])
    out["clock_skew_ms"] = None
    out["idle_by_span"] = out["idle_by_span_late"] = None
    shift = 0.0
    if skew is not None:
        shift = skew[0]
        out["clock_skew_ms"] = {"skew": skew[0] / 1e6,
                                "slack": skew[1] / 1e6, "pairs": skew[2]}
        for key, at in (("idle_by_span", shift),
                        ("idle_by_span_late", shift + skew[1])):
            out[key] = {g: ns / 1e9 for g, ns in
                        idle_by_span(ops, mine, at, lo, hi).items()}
    out["program_gaps"] = [
        [n, ns / 1e9] for n, ns in program_gaps(
            ops, bench + [sp[:3] for sp in program], shift, lo, hi, top)]
    return out


def kind_roofline(ctx: Mapping, kind: str) -> Optional[float]:
    """One kind's share of its roofline while the device runs it, in %:
    the least time of its layers, each max(flops / bf16 peak, bytes / HBM
    bandwidth) (``work.py``, ``peaks.json``), times the traced forwards,
    over the device seconds the trace attributes to those layers."""
    tr = ctx.get("trace") or {}
    seconds, work = tr.get("layers"), ctx.get("layer_work")
    if not seconds or not work or not ctx.get("forwards"):
        return None
    names = [n for n, w in work.items() if w["kind"] == kind]
    busy = sum(seconds.get(n, 0.0) for n in names)
    if busy <= 0:
        return None
    peaks = ctx["peaks"]
    least = sum(max(work[n]["flops"] / peaks["bf16_flops_per_s"],
                    work[n]["min_bytes"] / peaks["hbm_bytes_per_s"])
                for n in names)
    return 100.0 * least * ctx["forwards"] / busy


def idle_share(ctx: Mapping, group: str) -> Optional[float]:
    """Share of the traced window, in %, in which the device idles while
    the host is in the group's program spans (``IDLE_GROUPS``)."""
    tr = ctx.get("trace") or {}
    idle = tr.get("idle_by_span")
    if not idle or not tr.get("window_s"):
        return None
    return 100.0 * idle[group] / tr["window_s"]


def setup_spans(events: List[Mapping], tid: int, until: float
                ) -> Dict[str, float]:
    """Seconds by name of the program's spans in set-up: the spans of
    the ``obs.trace`` buffer on thread ``tid`` that end by ``until``
    (the tracer's clock), at the top level or directly under it."""
    evs = sorted((e for e in events if e["ph"] == "X" and e["tid"] == tid
                  and e["ts"] + e["dur"] <= until),
                 key=lambda e: (e["ts"], -e["dur"]))
    out: Dict[str, float] = {}
    stack: List[float] = []                 # ends of the open spans
    for e in evs:
        while stack and e["ts"] >= stack[-1]:
            stack.pop()
        if len(stack) <= 1:
            out[e["name"]] = out.get(e["name"], 0.0) + e["dur"]
        stack.append(e["ts"] + e["dur"])
    return out
