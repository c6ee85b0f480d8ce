"""The plain reference of served evolve analytics, in float64.

Nothing here imports the program.  Masks come from
``bench.reference.Snapshots`` (the event log replayed); PageRank is a
power iteration over a sparse adjacency; components come from
``scipy.sparse.csgraph``.  The conventions are the program's:

* PageRank treats every live edge as both directions (an edge counts at
  both of its ends), ranks dead nodes 0, spreads the mass of live nodes
  with no live edge (dangling) over the live nodes, starts uniform over
  them, damps by 0.85, and iterates here until the L1 step is at most
  1e-12;
* a component's label is the smallest live node id in it (live edges
  between live nodes only), and a dead node's label is the int32
  maximum.
"""
from __future__ import annotations

import zlib

import numpy as np
from scipy import sparse
from scipy.sparse import csgraph

from .history import History

DAMPING = 0.85
TOL = 1e-12
MAX_ITERS = 100_000
BIG = np.iinfo(np.int32).max


def crc(a: np.ndarray) -> int:
    return zlib.crc32(np.ascontiguousarray(a).tobytes())


def _adjacency(h: History, edge_mask: np.ndarray) -> sparse.csr_matrix:
    """Live-edge multiplicities, both directions: ``A[v, u]`` counts the
    live edges between ``u`` and ``v`` (a self loop twice)."""
    live = np.nonzero(edge_mask)[0]
    s, d = h.edge_src[live], h.edge_dst[live]
    n = h.num_nodes
    rows, cols = np.concatenate([d, s]), np.concatenate([s, d])
    return sparse.csr_matrix((np.ones(rows.size), (rows, cols)),
                             shape=(n, n))


def pagerank(h: History, node_mask: np.ndarray, edge_mask: np.ndarray, *,
             tol: float = TOL, max_iters: int = MAX_ITERS,
             cast=None) -> np.ndarray:
    """Masked PageRank to an L1 step of ``tol``.  ``cast``, if given, is
    applied to the ranks and contributions at every step (a lower
    precision, for the control), and the last step is returned when
    ``max_iters`` runs out; without it, not converging is an error."""
    rnd = cast or (lambda x: x)
    A = _adjacency(h, edge_mask)
    n = node_mask.astype(np.float64)
    deg = np.asarray(A.sum(axis=1)).ravel()
    inv = np.divide(1.0, deg, out=np.zeros(deg.size), where=deg > 0)
    n_live = max(n.sum(), 1.0)
    dangling_nodes = deg == 0
    pr = rnd(n / n_live)
    for _ in range(max_iters):
        agg = A @ rnd(pr * inv)
        dangling = pr[dangling_nodes].sum()
        new = rnd(n * ((1 - DAMPING) / n_live
                       + DAMPING * (agg + dangling / n_live)))
        step = np.abs(new - pr).sum()
        pr = new
        if step <= tol:
            return pr
    if cast is not None:
        return pr
    raise RuntimeError(f"PageRank reference not converged in {max_iters}")


def components(h: History, node_mask: np.ndarray,
               edge_mask: np.ndarray) -> np.ndarray:
    both = edge_mask & node_mask[h.edge_src] & node_mask[h.edge_dst]
    _, comp = csgraph.connected_components(_adjacency(h, both),
                                           directed=False)
    ids = np.arange(h.num_nodes)
    low = np.full(comp.max() + 1, h.num_nodes, np.int64)
    np.minimum.at(low, comp, ids)
    return np.where(node_mask, low[comp], BIG).astype(np.int32)
