"""Work of a looped transformer's prefill, counted from layer shapes and
from the compiled module: the yardstick of the ``prefill`` mode.

A layer is the plain mapping of ``configs/<arch>.py`` (``from_spec``
gives the same form for a program ``LayerSpec``).  Counted is what the
algorithm needs, not what an implementation does:

* ``macs``: an fc's N * C * K; causal attention's QK^T and PV over the
  positions each query sees, batch * heads * head_dim * S(S+1)/2 each
  (the program forms the whole S x S score matrix; that excess is not
  counted); 0 for norm, glu and eltwise layers;
* ``flops``: 2 per MAC;
* ``min_bytes`` of an fc and an attention layer: every operand read once
  and the output written once (attention reads q, k and v once and never
  writes its scores), at ``dtype_bytes`` per element.

``op_bytes`` reads, from the compiled module's text, the bytes each
top-level operation of the executable reads and writes: its output, and
each operand whole, or, where the fused computation only slices an
operand, the slices it takes.  XLA splits an RMSNorm into a row
reduction, an rsqrt and a multiply fused into the consumer's matmul, and
fuses the SwiGLU product into the down projection's matmul, so no single
op does a norm's or glu's whole work: their roofline is priced per op
(``normglu_roofline``).
"""
from __future__ import annotations

import math
import re
from typing import Dict, Iterable, List, Mapping, Optional

def from_spec(spec) -> dict:
    """The counting form of a program ``LayerSpec`` (the keys the
    configuration's layer list gives)."""
    d = {"name": spec.name, "kind": spec.kind, "N": spec.dim("N"),
         "C": spec.dim("C"), "K": spec.dim("K"), "src": list(spec.src)}
    meta = spec.meta
    if spec.kind == "attention":
        d.update(X=spec.dim("X"), batch=int(meta["batch"]),
                 seq=spec.dim("X"), heads=int(meta["heads"]),
                 kv_heads=int(meta.get("kv_heads", meta["heads"])),
                 causal=bool(meta.get("causal", 0)),
                 rope_theta=float(meta.get("rope_theta", 0.0)))
    if "eps" in meta:
        d["eps"] = float(meta["eps"])
    if "last_position" in meta:
        d["last_position"] = int(meta["last_position"])
    if "tied" in meta:
        d["tied"] = str(meta["tied"])
    return d


def attention_pairs(layer: Mapping) -> int:
    """(query, key) position pairs one head of one sequence scores."""
    s = int(layer["seq"])
    return s * (s + 1) // 2 if layer["causal"] else s * s


def macs(layer: Mapping) -> int:
    kind = layer["kind"]
    if kind == "fc":
        return int(layer["N"]) * int(layer["C"]) * int(layer["K"])
    if kind == "attention":
        return (2 * int(layer["batch"]) * int(layer["heads"])
                * int(layer["K"]) * attention_pairs(layer))
    if kind in ("norm", "glu", "eltwise"):
        return 0
    raise ValueError(f"no work count for kind {kind!r}")


def flops(layer: Mapping) -> int:
    return 2 * macs(layer)


def min_bytes(layer: Mapping, dtype_bytes: int = 4) -> int:
    kind = layer["kind"]
    if kind == "fc":
        n, c, k = int(layer["N"]), int(layer["C"]), int(layer["K"])
        elems = n * c + c * k + n * k
    elif kind == "attention":
        tokens = int(layer["batch"]) * int(layer["seq"])
        h, kv = int(layer["heads"]), int(layer["kv_heads"])
        elems = tokens * (h + 2 * kv) * int(layer["K"]) \
            + tokens * h * int(layer["K"])
    else:
        raise ValueError(f"no byte count for kind {kind!r}")
    return elems * dtype_bytes


def total_macs(layers: Iterable[Mapping]) -> int:
    """fc and attention MACs of a whole forward."""
    return sum(macs(l) for l in layers)


# ---------------------------------------------------------------------------
# bytes per top-level op, from the compiled module's text
# ---------------------------------------------------------------------------

_BYTES = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s16": 2,
          "u16": 2, "f32": 4, "s32": 4, "u32": 4, "f64": 8, "s64": 8,
          "u64": 8}
_ARRAY = re.compile(r"\b(" + "|".join(_BYTES) + r")\[([\d,]*)\]")
_HEADER = re.compile(r"^(?:ENTRY\s+)?%?([\w.-]+)\s*\((.*)\)\s*->.*\{\s*$")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.-]+)\s*=\s*(.*?)\s+"
                    r"([\w-]+)\((.*?)\)(?:,\s|$)")
_PARAM = re.compile(r"parameter\((\d+)\)")
_CALLS = re.compile(r"calls=%?([\w.-]+)")
_SLICES = ("slice", "dynamic-slice")
_VIEWS = ("bitcast", "reshape")
#: instructions that run no device operation of their own
_NO_OP = ("parameter", "constant", "get-tuple-element", "tuple", "bitcast")


def shape_bytes(text: str) -> int:
    """Bytes of every array shape in ``text`` (a tuple sums its parts)."""
    total = 0
    for dtype, dims in _ARRAY.findall(text):
        total += _BYTES[dtype] * math.prod(int(d) for d in dims.split(",")
                                           if d)
    return total


def _computations(hlo_text: str) -> Dict[str, List[tuple]]:
    """{computation: [(name, shape text, opcode, operand names, line)]},
    ``ENTRY`` for the entry computation."""
    comps: Dict[str, List[tuple]] = {}
    current: Optional[List[tuple]] = None
    for line in hlo_text.splitlines():
        if current is None:
            head = _HEADER.match(line)
            if head:
                key = "ENTRY" if line.startswith("ENTRY") else head.group(1)
                current = comps.setdefault(key, [])
            continue
        if line.startswith("}"):
            current = None
            continue
        m = _INSTR.match(line)
        if m:
            operands = [o.strip().split(" ")[-1].lstrip("%")
                        for o in m.group(4).split(",") if o.strip()]
            current.append((m.group(1), m.group(2), m.group(3), operands,
                            line))
    return comps


def _param_reads(body: List[tuple]) -> Dict[int, int]:
    """{parameter number: bytes the fused computation reads of it}: the
    whole parameter, or the slices it takes where slices are all that
    use it (through bitcasts and reshapes)."""
    users: Dict[str, List[tuple]] = {}
    for ins in body:
        for o in ins[3]:
            users.setdefault(o, []).append(ins)

    def read(name: str, whole: int) -> int:
        uses = users.get(name, [])
        if not uses:
            return whole
        if all(u[2] in _SLICES for u in uses):
            return sum(shape_bytes(u[1]) for u in uses)
        if all(u[2] in _VIEWS for u in uses):
            return min(whole, sum(read(u[0], whole) for u in uses))
        return whole

    out: Dict[int, int] = {}
    for name, shape, opcode, _, line in body:
        p = _PARAM.search(line) if opcode == "parameter" else None
        if p:
            out[int(p.group(1))] = read(name, shape_bytes(shape))
    return out


def op_bytes(hlo_text: str) -> Dict[str, int]:
    """{entry instruction: bytes it writes and reads} of a compiled
    module's text (``FusedNetwork.compiled_text``)."""
    comps = _computations(hlo_text)
    entry = comps.get("ENTRY", [])
    sizes = {name: shape_bytes(shape) for name, shape, *_ in entry}
    out: Dict[str, int] = {}
    for name, shape, opcode, operands, line in entry:
        if opcode in _NO_OP:
            continue
        calls = _CALLS.search(line) if opcode == "fusion" else None
        reads = _param_reads(comps.get(calls.group(1), [])) if calls else {}
        out[name] = shape_bytes(shape) + sum(
            reads.get(i, sizes.get(o, 0)) for i, o in enumerate(operands))
    return out
