"""Bytes a kernel call needs, counted at the history's own sizes.

A device trace names each kernel call by its HLO instruction, whose text
carries the operand shapes, e.g.::

    %delta_apply_chain_batched_pallas.1 = u32[2,152,128]{...} custom-call(
        u32[2,152,128]{...} %base, u32[2,4,152,128]{...} %adds,
        u32[2,4,152,128]{...} %dels), custom_call_target="tpu_custom_call", ...

Only the call's batch and chain length are read from that text.  The
bytes are those the algorithm needs for the history's own node and edge
slots, so neither the kernel's padding nor the store capacity's
never-born slots count, and a change of implementation leaves them as
they are:

* delta apply: per chain of the batch, a base plane, the K add and the K
  delete planes, and the landed plane, each one bit per node slot or per
  edge slot (a call on planes at least as wide as the edge plane works
  on edges, a narrower one on nodes);
* segment sum (the loader's degree sums, one column): a 4-byte value and
  a 4-byte index read per edge slot, a 4-byte sum written per node slot.
"""
from __future__ import annotations

import re

_SHAPE = re.compile(r"\b[a-z]+\d*\[([\d,]*)\]")


def plane_bytes(slots: int) -> int:
    return 4 * -(-int(slots) // 32)


def operand_dims(text: str) -> list[list[int]]:
    """The dimensions of each operand of a custom call's instruction."""
    args = text.split("custom-call(", 1)[-1].split("custom_call_target=")[0]
    return [[int(d) for d in dims.split(",") if d]
            for dims in _SHAPE.findall(args)]


def delta_apply_bytes(text: str, nodes: int, edges: int) -> int:
    ops = operand_dims(text)
    if not ops:
        return 0
    base = ops[0]
    k = ops[1][1] if len(ops) > 1 and len(ops[1]) == len(base) + 1 else 0
    words = 1
    for d in base[1:]:
        words *= d
    slots = edges if words >= plane_bytes(edges) // 4 else nodes
    return base[0] * (2 + 2 * k) * plane_bytes(slots)


def segment_sum_bytes(text: str, nodes: int, edges: int) -> int:
    return 4 * (2 * int(edges) + int(nodes))
