"""Plain float64 reference for served evolve analytics.

Independent of the program's solvers: snapshot masks come from the
``events.replay`` oracle (every event up to ``t`` applied to the empty
graph), PageRank is a plain numpy power iteration in float64, and
components come from a union-find in Python.  The conventions are the
program's:

* PageRank treats every live edge as both directions, ranks dead nodes
  0, spreads the mass of live nodes with no live edge (dangling) over the
  live nodes, starts uniform over them, damps by 0.85, and iterates here
  until the L1 step is at most 1e-12;
* a component's label is the smallest live node id in it (live edges
  only), and a dead node's label is the int32 maximum.
"""
from __future__ import annotations

import zlib

import numpy as np

from repro.core.events import replay

DAMPING = 0.85
TOL = 1e-12
BIG = np.iinfo(np.int32).max


def crc(a: np.ndarray) -> int:
    return zlib.crc32(np.ascontiguousarray(a).tobytes())


def masks(uni, ev, t: int) -> tuple[np.ndarray, np.ndarray]:
    st = replay(uni, ev, t)
    return st.node_mask, st.edge_mask


def pagerank(src: np.ndarray, dst: np.ndarray, node_mask: np.ndarray,
             edge_mask: np.ndarray, max_iters: int = 100_000) -> np.ndarray:
    n = node_mask.astype(np.float64)
    N = n.size
    live = np.nonzero(edge_mask)[0]
    s, d = src[live], dst[live]
    deg = (np.bincount(s, minlength=N)
           + np.bincount(d, minlength=N)).astype(np.float64)
    inv = np.divide(1.0, deg, out=np.zeros(N), where=deg > 0)
    n_live = max(n.sum(), 1.0)
    pr = n / n_live
    for _ in range(max_iters):
        c = pr * inv
        agg = (np.bincount(d, weights=c[s], minlength=N)
               + np.bincount(s, weights=c[d], minlength=N))
        dangling = pr[deg == 0].sum()
        new = n * ((1 - DAMPING) / n_live
                   + DAMPING * (agg + dangling / n_live))
        step = np.abs(new - pr).sum()
        pr = new
        if step <= TOL:
            return pr
    raise RuntimeError(f"PageRank reference not converged in {max_iters}")


def components(src: np.ndarray, dst: np.ndarray, node_mask: np.ndarray,
               edge_mask: np.ndarray) -> np.ndarray:
    parent = list(range(node_mask.size))

    def find(x: int) -> int:
        while parent[x] != x:
            parent[x] = parent[parent[x]]
            x = parent[x]
        return x

    for e in np.nonzero(edge_mask)[0]:
        u, v = int(src[e]), int(dst[e])
        if node_mask[u] and node_mask[v]:
            a, b = find(u), find(v)
            parent[max(a, b)] = min(a, b)    # the root stays the minimum
    labels = np.array([find(i) for i in range(node_mask.size)], np.int64)
    return np.where(node_mask, labels, BIG).astype(np.int32)
