"""Loop-aware roofline accounting from optimized HLO text.

``compiled.cost_analysis()`` visits each ``while`` body **once**, so a
61-layer scanned transformer reports 1/61 of its real FLOPs.  This module
re-derives the three roofline inputs directly from ``compiled.as_text()``:

* **flops**            — 2·prod(out)·K for every ``dot`` (K = contracted
  extent), with each computation's total multiplied by the product of
  enclosing ``while`` trip counts (parsed from the loop condition);
* **hbm bytes**        — operand+output bytes of every *top-level* op in
  each computation (fusion bodies are excluded: a fusion's traffic is its
  operands/outputs, which is exactly how XLA:TPU schedules HBM), again
  trip-count-multiplied;
* **collective bytes** — output bytes of all-gather / all-reduce /
  reduce-scatter / all-to-all / collective-permute ops, trip-count-
  multiplied, reported per collective kind.

This is an analytical model of the compiled program, not a simulation —
exactly what the dry-run needs on a CPU container.
"""
from __future__ import annotations

import dataclasses
import re
from collections import defaultdict

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1, "c64": 8, "c128": 16, "s4": 1, "u4": 1,
}

_SHAPE_RE = re.compile(r"(" + "|".join(_DTYPE_BYTES) + r")\[([\d,]*)\]")
_DEF_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_COMP_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*(?:\(.*?\))?\s*->.*{\s*$")
_CALL_ATTR_RE = re.compile(r"(?:condition|body|calls|to_apply|branch_computations)="
                           r"(%?[\w.\-]+|\{[^}]*\})")
COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")

# Published peaks of one chip, keyed by ``jax.Device.device_kind``: the
# roofline's denominators, for the dry run's target and for measured runs.
PEAKS_SOURCE = 'Google Cloud documentation, "TPU v5e", per-chip specifications'
DEVICE_PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes": 16e9, "hbm_bytes_per_s": 819e9,
                    # 1,600 Gbit/s of ICI per chip over 4 links
                    "ici_link_bytes_per_s": 50e9},
}
DRYRUN_TARGET = "TPU v5 lite"   # the meshes launch/dryrun.py compiles for


def device_peaks(kind: str) -> dict:
    """Peaks of a device kind; an unknown kind raises instead of guessing."""
    try:
        return DEVICE_PEAKS[kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {kind!r}; add "
                       f"them to DEVICE_PEAKS with their source") from None


def shape_bytes(text: str) -> int:
    """Sum bytes over every dtype[shape] occurrence in a type string."""
    total = 0
    for m in _SHAPE_RE.finditer(text):
        dt, dims = m.group(1), m.group(2)
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _shape_dims(text: str) -> list[int]:
    m = _SHAPE_RE.search(text)
    if not m:
        return []
    dims = m.group(2)
    return [int(d) for d in dims.split(",")] if dims else []


@dataclasses.dataclass
class Instruction:
    name: str
    rhs: str
    out_type: str
    opcode: str


@dataclasses.dataclass
class Computation:
    name: str
    instructions: list[Instruction]


def parse_hlo(text: str) -> dict[str, Computation]:
    comps: dict[str, Computation] = {}
    cur: Computation | None = None
    for line in text.splitlines():
        if line.rstrip().endswith("{") and ("->" in line or line.startswith("ENTRY")):
            m = _COMP_RE.match(line.strip())
            if m:
                cur = Computation(m.group(1), [])
                comps[cur.name] = cur
            continue
        if line.strip() == "}":
            cur = None
            continue
        if cur is None:
            continue
        m = _DEF_RE.match(line)
        if not m:
            continue
        name, rhs = m.group(1), m.group(2)
        tm = re.match(r"((?:\([^)]*\))|(?:[\w\[\],{}\d]+))\s+([\w\-]+)", rhs)
        if not tm:
            continue
        out_type, opcode = tm.group(1), tm.group(2)
        cur.instructions.append(Instruction(name, rhs, out_type, opcode))
    return comps


def _trip_count(cond: Computation) -> int:
    """Scan-style loop conditions compare the induction var with a
    constant; take the largest integer constant found."""
    best = 1
    for ins in cond.instructions:
        for m in re.finditer(r"constant\((\d+)\)", ins.rhs):
            best = max(best, int(m.group(1)))
    return best


def _dot_flops(ins: Instruction, shapes: dict[str, str]) -> float:
    out_elems = 1
    for d in _shape_dims(ins.out_type):
        out_elems *= d
    m = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", ins.rhs)
    ops = re.findall(r"%([\w.\-]+)", ins.rhs)
    if not m or not ops:
        return 2.0 * out_elems  # fallback
    lhs_type = shapes.get(ops[0], "")
    dims = _shape_dims(lhs_type)
    k = 1
    for ci in (int(x) for x in m.group(1).split(",") if x):
        if ci < len(dims):
            k *= dims[ci]
    return 2.0 * out_elems * k


def analyze(text: str) -> dict:
    """Returns {'flops', 'hbm_bytes', 'collective_bytes',
    'collectives': {kind: bytes}, 'per_comp': {...}}."""
    comps = parse_hlo(text)
    # global symbol table name -> out_type (names are unique in HLO dumps)
    shapes: dict[str, str] = {}
    for c in comps.values():
        for ins in c.instructions:
            shapes[ins.name] = ins.out_type

    # computations called as fusion bodies / reducers: exclude from direct
    # accounting (their traffic is the call site's operands/outputs)
    fused_bodies: set[str] = set()
    called_by: dict[str, list[tuple[str, int]]] = defaultdict(list)
    trip_of_body: dict[str, int] = {}
    for c in comps.values():
        for ins in c.instructions:
            attrs = dict()
            for m in re.finditer(r"(condition|body|calls|to_apply)=%?([\w.\-]+)",
                                 ins.rhs):
                attrs[m.group(1)] = m.group(2)
            if ins.opcode == "while":
                cond = attrs.get("condition")
                body = attrs.get("body")
                tc = _trip_count(comps[cond]) if cond in comps else 1
                if body in comps:
                    trip_of_body[body] = tc
                    called_by[body].append((c.name, tc))
                if cond in comps:
                    fused_bodies.add(cond)  # negligible; skip
            elif ins.opcode == "fusion":
                if "calls" in attrs:
                    fused_bodies.add(attrs["calls"])
            elif "to_apply" in attrs:  # reduce/scatter combiners
                fused_bodies.add(attrs["to_apply"])

    # multiplier per computation: product of trip counts on the call chain
    def multiplier(name: str, seen=None) -> float:
        seen = seen or set()
        if name in seen:
            return 1.0
        seen = seen | {name}
        if not called_by.get(name):
            return 1.0
        total = 0.0
        for caller, tc in called_by[name]:
            total += tc * multiplier(caller, seen)
        return max(total, 1.0)

    flops = 0.0
    hbm = 0.0
    coll: dict[str, float] = defaultdict(float)
    per_comp: dict[str, dict] = {}
    for c in comps.values():
        if c.name in fused_bodies:
            continue
        mult = multiplier(c.name)
        c_fl = 0.0
        c_hbm = 0.0
        for ins in c.instructions:
            if ins.opcode in ("dot", "convolution"):
                c_fl += _dot_flops(ins, shapes)
            out_b = shape_bytes(ins.out_type)
            if ins.opcode in ("fusion", "dot", "convolution", "copy",
                              "dynamic-update-slice", "dynamic-slice",
                              "gather", "scatter", "sort", "transpose",
                              "reshape", "broadcast", "reduce", "concatenate",
                              "slice", "convert", "select-and-scatter",
                              "pad", "iota", "rng-bit-generator") or \
                    ins.opcode.startswith("all-") or \
                    ins.opcode in ("reduce-scatter", "collective-permute"):
                in_b = 0
                for op in re.findall(r"%([\w.\-]+)", ins.rhs):
                    if op in shapes:
                        in_b += shape_bytes(shapes[op])
                c_hbm += out_b + in_b
            for kind in COLLECTIVES:
                if ins.opcode == kind or ins.opcode == kind + "-start":
                    coll[kind] += out_b * mult
        flops += c_fl * mult
        hbm += c_hbm * mult
        per_comp[c.name] = {"mult": mult, "flops": c_fl, "hbm": c_hbm}

    return {"flops": flops, "hbm_bytes": hbm,
            "collective_bytes": sum(coll.values()),
            "collectives": dict(coll), "per_comp": per_comp}
