"""Bytes the fixpoint solvers' device programs must move.

One iteration of the segment PageRank program
(``_pagerank_fixpoint_kernel`` in the program's ``graph/algorithms.py``)
over ``edges`` padded live-edge rows and ``nodes`` node slots reads, per
edge row, its source, destination and liveness and the two gathered
contributions, and scatters two sums (4 bytes each: 28 bytes); per node
slot it reads the rank, the inverse degree, the node mask and the summed
contributions and writes the new rank (4 bytes each: 20 bytes).  Only
the loop's iterations count: the degrees, computed once a solve, and
the start's projection are left out, so the count is a floor.
"""
from __future__ import annotations


def pagerank_iteration_bytes(nodes: int, edges: int) -> int:
    return 28 * int(edges) + 20 * int(nodes)
