"""Graph analytics over GraphPool bitmap planes.

Every algorithm takes the union graph's edge list plus a *packed edge
bitmap* (one GraphPool plane) and runs on the masked subgraph — this is
the paper's "execute analyses against overlaid snapshots" path (§6,
bitmap-penalty experiment).  ``vmap`` over stacked planes evaluates many
snapshots at once (multipoint analytics).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core import bitmaps as bm
from ..runtime.spans import timed


def edge_mask_from_plane(plane: jnp.ndarray, num_edges: int) -> jnp.ndarray:
    return bm.unpack(plane, num_edges)


@functools.partial(jax.jit, static_argnames=("num_nodes", "iters"))
def pagerank(edge_src: jnp.ndarray, edge_dst: jnp.ndarray,
             edge_plane: jnp.ndarray, node_plane: jnp.ndarray, *,
             num_nodes: int, iters: int = 20,
             damping: float = 0.85) -> jnp.ndarray:
    """Masked PageRank treating undirected edges as both directions."""
    E = edge_src.shape[0]
    emask = bm.unpack(edge_plane, E).astype(jnp.float32)
    nmask = bm.unpack(node_plane, num_nodes).astype(jnp.float32)
    deg = (jax.ops.segment_sum(emask, edge_src, num_segments=num_nodes)
           + jax.ops.segment_sum(emask, edge_dst, num_segments=num_nodes))
    inv_deg = jnp.where(deg > 0, 1.0 / jnp.maximum(deg, 1), 0.0)
    n_live = jnp.maximum(nmask.sum(), 1.0)

    def step(pr, _):
        contrib = pr * inv_deg
        agg = (jax.ops.segment_sum(contrib[edge_src] * emask, edge_dst,
                                   num_segments=num_nodes)
               + jax.ops.segment_sum(contrib[edge_dst] * emask, edge_src,
                                     num_segments=num_nodes))
        dangling = (pr * (deg == 0)).sum()
        pr2 = nmask * ((1 - damping) / n_live
                       + damping * (agg + dangling / n_live))
        return pr2, None

    pr0 = nmask / n_live
    pr, _ = jax.lax.scan(step, pr0, None, length=iters)
    return pr


@functools.partial(jax.jit, static_argnames=("num_nodes",))
def degrees_masked(edge_src, edge_dst, edge_plane, *, num_nodes: int):
    E = edge_src.shape[0]
    emask = bm.unpack(edge_plane, E).astype(jnp.int32)
    return (jax.ops.segment_sum(emask, edge_src, num_segments=num_nodes)
            + jax.ops.segment_sum(emask, edge_dst, num_segments=num_nodes))


@functools.partial(jax.jit, static_argnames=("num_nodes", "iters"))
def connected_components(edge_src, edge_dst, edge_plane, node_plane, *,
                         num_nodes: int, iters: int = 50):
    """Label propagation: min-label flooding (HashMin), masked."""
    E = edge_src.shape[0]
    emask = bm.unpack(edge_plane, E)
    nmask = bm.unpack(node_plane, num_nodes)
    big = jnp.iinfo(jnp.int32).max
    labels0 = jnp.where(nmask, jnp.arange(num_nodes, dtype=jnp.int32), big)

    def step(lab, _):
        src_l = jnp.where(emask, lab[edge_src], big)
        dst_l = jnp.where(emask, lab[edge_dst], big)
        m1 = jax.ops.segment_min(src_l, edge_dst, num_segments=num_nodes)
        m2 = jax.ops.segment_min(dst_l, edge_src, num_segments=num_nodes)
        new = jnp.minimum(lab, jnp.minimum(m1, m2))
        return jnp.where(nmask, new, big), None

    labels, _ = jax.lax.scan(step, labels0, None, length=iters)
    return labels


def triangle_count(edge_src: np.ndarray, edge_dst: np.ndarray,
                   edge_mask: np.ndarray, num_nodes: int) -> int:
    """Host-side exact triangle count on the masked subgraph (numpy;
    used by evolution analyses — 'how many new triangles this year')."""
    eid = np.nonzero(edge_mask)[0]
    s, d = edge_src[eid], edge_dst[eid]
    lo, hi = np.minimum(s, d), np.maximum(s, d)
    keep = lo != hi
    pairs = np.unique(np.stack([lo[keep], hi[keep]], 1), axis=0)
    adj: dict[int, set] = {}
    for a, b in pairs:
        adj.setdefault(int(a), set()).add(int(b))
    count = 0
    for a, nbrs in adj.items():
        for b in nbrs:
            count += len(nbrs & adj.get(b, set()))
    return count // 1  # each triangle counted once: a<b<c ordering


def multi_snapshot_pagerank(edge_src, edge_dst, edge_planes, node_planes, *,
                            num_nodes: int, iters: int = 20):
    """vmap over GraphPool planes: PageRank for G snapshots in one shot."""
    fn = functools.partial(pagerank, num_nodes=num_nodes, iters=iters)
    return jax.vmap(lambda ep, np_: fn(edge_src, edge_dst, ep, np_))(
        jnp.asarray(edge_planes), jnp.asarray(node_planes))


# ---------------------------------------------------------------------------
# incremental / warm-started variants (temporal analytics, core/temporal.py)
# ---------------------------------------------------------------------------
#
# The fixpoint solvers below iterate to a *convergence criterion* instead of
# a fixed step count, so a warm start (the previous timepoint's result with
# only the delta-touched frontier reset) buys real iterations: between two
# nearby snapshots the solution barely moves, and the solver exits after a
# couple of sweeps instead of re-running the full cold schedule.  Cold and
# warm starts converge to the same fixpoint, so incremental results match a
# per-snapshot recompute up to the tolerance.


# iteration caps of the fixpoint solvers: the defaults of the evolve
# operators (core/temporal.py PageRankOp, ComponentsOp), which a served
# document runs, so compile_fixpoints compiles the programs they call
PAGERANK_MAX_ITERS = 200
COMPONENTS_MAX_ITERS = 4096


def fixpoint_lengths(num_edges: int) -> tuple[int, ...]:
    """The padded live-edge lengths the fixpoint solvers use on a universe
    of ``num_edges`` edge slots: ``m · 2^k`` for ``m`` in 4..7 and
    ``k ≥ 7`` (512, 640, 768, 896, 1024, 1280, ...), four to a doubling,
    up to the first length that holds every slot.

    A live count is padded to the smallest length that holds it, so a
    universe has one compiled program per solver and length, about
    ``4 · log2(num_edges / 512)`` of them, and :func:`compile_fixpoints`
    can compile them all before serving; padding wastes under a quarter
    of the scattered elements.  Every length is a multiple of 128, the
    TPU's lane width."""
    out: list[int] = []
    k = 7
    while not out or out[-1] < num_edges:
        out += [m << k for m in (4, 5, 6, 7)]
        k += 1
    return tuple(n for i, n in enumerate(out)
                 if i == 0 or out[i - 1] < num_edges)


class Solved(NamedTuple):
    """A fixpoint solve: its ``value``, the iterations it ran, and the
    edge rows each iteration scattered (the padded live-edge length; the
    live count on the dense PageRank path)."""
    value: np.ndarray
    iters: int
    edges: int


def _edge_length(n: int, num_edges: int) -> int:
    """The length :func:`fixpoint_lengths` pads ``n`` live edges to."""
    for length in fixpoint_lengths(num_edges):
        if length >= n:
            return length
    raise ValueError(f"{n} live edges in a universe of {num_edges} slots")


def _compact_edges(edge_src: np.ndarray, edge_dst: np.ndarray,
                   edge_mask: np.ndarray
                   ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Drop masked-out edge slots before solving: after churn, live edges
    are a small fraction of the slot universe, and scatter cost scales
    with the number of *scattered elements*, masked or not.  The live
    edges are padded to the :func:`fixpoint_lengths` length of their
    count.  Padding rows are (0, 0) with live=0 — segment-summed with
    zero mass, exactly like a masked slot, so the padding leaves every
    result bit for bit as it is."""
    live = np.nonzero(edge_mask)[0]
    Ec = _edge_length(live.size, edge_mask.size)
    es = np.zeros(Ec, np.int32)
    ed = np.zeros(Ec, np.int32)
    lv = np.zeros(Ec, np.float32)
    es[: live.size] = edge_src[live]
    ed[: live.size] = edge_dst[live]
    lv[: live.size] = 1.0
    return es, ed, lv


@functools.partial(jax.jit, static_argnames=("num_nodes", "max_iters"))
def _pagerank_fixpoint_kernel(edge_src, edge_dst, edge_live, node_plane,
                              pr0, damping, tol, *, num_nodes: int,
                              max_iters: int):
    nmask = bm.unpack(node_plane, num_nodes).astype(jnp.float32)
    deg = (jax.ops.segment_sum(edge_live, edge_src, num_segments=num_nodes)
           + jax.ops.segment_sum(edge_live, edge_dst, num_segments=num_nodes))
    inv_deg = jnp.where(deg > 0, 1.0 / jnp.maximum(deg, 1), 0.0)
    n_live = jnp.maximum(nmask.sum(), 1.0)
    # project the start onto the live-node simplex (masks may have changed)
    pr0 = jnp.maximum(pr0, 0.0) * nmask
    s0 = pr0.sum()
    pr0 = jnp.where(s0 > 0, pr0 / jnp.maximum(s0, 1e-30), nmask / n_live)

    def step(pr):
        contrib = pr * inv_deg
        agg = (jax.ops.segment_sum(contrib[edge_src] * edge_live, edge_dst,
                                   num_segments=num_nodes)
               + jax.ops.segment_sum(contrib[edge_dst] * edge_live, edge_src,
                                     num_segments=num_nodes))
        dangling = (pr * (deg == 0)).sum()
        return nmask * ((1 - damping) / n_live
                        + damping * (agg + dangling / n_live))

    def cond(carry):
        _, delta, i = carry
        return (delta > tol) & (i < max_iters)

    def body(carry):
        pr, _, i = carry
        pr2 = step(pr)
        return pr2, jnp.abs(pr2 - pr).sum(), i + 1

    pr, _, iters = jax.lax.while_loop(
        cond, body, (pr0, jnp.float32(jnp.inf), jnp.int32(0)))
    return pr, iters


@functools.partial(jax.jit, static_argnames=("max_iters",))
def _pagerank_fixpoint_dense(A, nmask, pr0, damping, tol, *,
                             max_iters: int):
    """Dense-adjacency variant of the same iteration: ``agg = A @
    (pr/deg)`` with ``A[i, j]`` = live-edge multiplicity — identical math
    to the segment formulation, but a matvec instead of scatters (XLA-CPU
    scatter cost is per scattered element; for small N the N² matvec is
    an order of magnitude cheaper)."""
    deg = A.sum(1)
    inv_deg = jnp.where(deg > 0, 1.0 / jnp.maximum(deg, 1), 0.0)
    n_live = jnp.maximum(nmask.sum(), 1.0)
    pr0 = jnp.maximum(pr0, 0.0) * nmask
    s0 = pr0.sum()
    pr0 = jnp.where(s0 > 0, pr0 / jnp.maximum(s0, 1e-30), nmask / n_live)

    def body(carry):
        pr, _, i = carry
        agg = A @ (pr * inv_deg)
        dangling = (pr * (deg == 0)).sum()
        pr2 = nmask * ((1 - damping) / n_live
                       + damping * (agg + dangling / n_live))
        return pr2, jnp.abs(pr2 - pr).sum(), i + 1

    pr, _, iters = jax.lax.while_loop(
        lambda c: (c[1] > tol) & (c[2] < max_iters), body,
        (pr0, jnp.float32(jnp.inf), jnp.int32(0)))
    return pr, iters


# above this node count the dense [N, N] adjacency (4·N² bytes) stops
# paying for itself and the compact segment kernel takes over
DENSE_PAGERANK_MAX_NODES = 1024


def pagerank_fixpoint(edge_src, edge_dst, edge_plane, node_plane, pr0, *,
                      num_nodes: int, max_iters: int = PAGERANK_MAX_ITERS,
                      damping: float = 0.85, tol: float = 1e-6,
                      force_impl: str | None = None) -> Solved:
    """Masked PageRank iterated until the L1 step change drops under
    ``tol`` (or ``max_iters``).  ``pr0`` is the starting vector — pass the
    previous snapshot's ranks (with the touched frontier reset) for the
    incremental path, or a uniform vector for a cold solve.  Returns
    :class:`Solved` ``(pr, iters, edges)``; the fixpoint is unique, so
    the result does not depend on ``pr0`` beyond the tolerance.

    Host wrapper, under one ``solve.fixpoint`` span: compacts the edge
    list to the live slots and picks the dense-matvec kernel for small
    node universes (``DENSE_PAGERANK_MAX_NODES``) or the segment kernel
    above it, on live edges padded to a :func:`fixpoint_lengths` length —
    same semantics as solving over the full masked slot universe, at
    live-edge cost.  ``force_impl`` ("dense" | "segment") pins the
    kernel, for the equivalence tests."""
    edge_src = np.asarray(edge_src)
    edge_dst = np.asarray(edge_dst)
    E = edge_src.shape[0]
    impl = force_impl or ("dense" if num_nodes <= DENSE_PAGERANK_MAX_NODES
                          else "segment")
    with timed("solve.fixpoint", op="pagerank") as sp:
        emask = bm.np_unpack(np.asarray(edge_plane), E)
        if impl == "dense":
            nmask = bm.np_unpack(np.asarray(node_plane), num_nodes
                                 ).astype(np.float32)
            live = np.nonzero(emask)[0]
            A = np.zeros((num_nodes, num_nodes), np.float32)
            np.add.at(A, (edge_src[live], edge_dst[live]), 1.0)
            np.add.at(A, (edge_dst[live], edge_src[live]), 1.0)
            out = Solved(*_pagerank_dense(A, nmask, pr0, damping, tol,
                                          max_iters), live.size)
        else:
            es, ed, lv = _compact_edges(edge_src, edge_dst, emask)
            out = Solved(*_pagerank_segment(es, ed, lv, node_plane, pr0,
                                            damping, tol, num_nodes,
                                            max_iters), es.size)
        sp.set(iters=out.iters, edges=out.edges)
    return out


def _pagerank_dense(A, nmask, pr0, damping, tol, max_iters):
    pr, iters = _pagerank_fixpoint_dense(
        jnp.asarray(A), jnp.asarray(nmask), jnp.asarray(pr0, jnp.float32),
        jnp.float32(damping), jnp.float32(tol), max_iters=max_iters)
    return np.asarray(pr), int(iters)


def _pagerank_segment(es, ed, lv, node_plane, pr0, damping, tol, num_nodes,
                      max_iters):
    pr, iters = _pagerank_fixpoint_kernel(
        jnp.asarray(es), jnp.asarray(ed), jnp.asarray(lv),
        jnp.asarray(node_plane), jnp.asarray(pr0, jnp.float32),
        jnp.float32(damping), jnp.float32(tol),
        num_nodes=num_nodes, max_iters=max_iters)
    return np.asarray(pr), int(iters)


def pagerank_warm_start(prev_pr: np.ndarray, node_mask: np.ndarray,
                        touched: np.ndarray) -> np.ndarray:
    """Build a warm-start vector from the previous ranks: delta-touched
    nodes (endpoints of changed edges, added/removed nodes) are reset to
    the uniform baseline so stale mass does not slow convergence; every
    other live node keeps its rank."""
    n_live = max(int(node_mask.sum()), 1)
    pr0 = np.where(node_mask, np.maximum(prev_pr, 0.0), 0.0).astype(np.float32)
    if touched.size:
        t = touched[touched < pr0.size]
        pr0[t] = 1.0 / n_live
    pr0 *= node_mask
    s = pr0.sum()
    return (pr0 / s if s > 0
            else node_mask.astype(np.float32) / n_live)


@functools.partial(jax.jit, static_argnames=("num_nodes", "max_iters"))
def _cc_fixpoint_kernel(edge_src, edge_dst, edge_live, node_plane, labels0,
                        *, num_nodes: int, max_iters: int):
    nmask = bm.unpack(node_plane, num_nodes)
    big = jnp.iinfo(jnp.int32).max
    labels0 = jnp.where(nmask, labels0.astype(jnp.int32), big)
    emask = edge_live > 0

    def sweep(lab):
        src_l = jnp.where(emask, lab[edge_src], big)
        dst_l = jnp.where(emask, lab[edge_dst], big)
        m1 = jax.ops.segment_min(src_l, edge_dst, num_segments=num_nodes)
        m2 = jax.ops.segment_min(dst_l, edge_src, num_segments=num_nodes)
        new = jnp.minimum(lab, jnp.minimum(m1, m2))
        return jnp.where(nmask, new, big)

    def cond(carry):
        _, changed, i = carry
        return changed & (i < max_iters)

    def body(carry):
        lab, _, i = carry
        new = sweep(lab)
        return new, jnp.any(new != lab), i + 1

    labels, _, iters = jax.lax.while_loop(
        cond, body, (labels0, jnp.bool_(True), jnp.int32(0)))
    return labels, iters


def connected_components_fixpoint(edge_src, edge_dst, edge_plane, node_plane,
                                  labels0, *, num_nodes: int,
                                  max_iters: int = COMPONENTS_MAX_ITERS
                                  ) -> Solved:
    """HashMin label flooding run to its fixpoint (no label changes).

    Starting labels must satisfy the warm-start contract: within every
    component the minimum starting label equals the component's true label
    (the min live node id), and no node starts below its component's true
    label.  ``arange`` (cold) and the incremental reset of
    :func:`cc_warm_labels` both satisfy it, and then the fixpoint is
    exactly the cold answer.  Returns :class:`Solved`
    ``(labels, iters, edges)``.

    Host wrapper compacting to live edges padded to a
    :func:`fixpoint_lengths` length, under one ``solve.fixpoint`` span,
    like :func:`pagerank_fixpoint`."""
    E = np.asarray(edge_src).shape[0]
    with timed("solve.fixpoint", op="components") as sp:
        emask = bm.np_unpack(np.asarray(edge_plane), E)
        es, ed, lv = _compact_edges(np.asarray(edge_src),
                                    np.asarray(edge_dst), emask)
        out = Solved(*_components_segment(es, ed, lv, node_plane, labels0,
                                          num_nodes, max_iters), es.size)
        sp.set(iters=out.iters, edges=out.edges)
    return out


def _components_segment(es, ed, lv, node_plane, labels0, num_nodes,
                        max_iters):
    labels, iters = _cc_fixpoint_kernel(
        jnp.asarray(es), jnp.asarray(ed), jnp.asarray(lv),
        jnp.asarray(node_plane), jnp.asarray(labels0),
        num_nodes=num_nodes, max_iters=max_iters)
    return np.asarray(labels), int(iters)


def compile_fixpoints(num_nodes: int, num_edges: int) -> int:
    """Compiles, before serving, every program the two fixpoint solvers
    run on a universe of ``num_nodes`` node and ``num_edges`` edge slots:
    one per :func:`fixpoint_lengths` length and solver (the dense
    PageRank kernel once, where the universe takes it).  Each runs once,
    on no live edge, through the same host calls a solve makes.  Returns
    the number of programs."""
    plane = np.zeros(bm.num_words(num_nodes), np.uint32)
    pr0 = np.zeros(num_nodes, np.float32)
    labels0 = np.arange(num_nodes, dtype=np.int32)
    lengths = fixpoint_lengths(num_edges)
    for n in lengths:
        es = np.zeros(n, np.int32)
        lv = np.zeros(n, np.float32)
        _components_segment(es, es, lv, plane, labels0, num_nodes,
                            COMPONENTS_MAX_ITERS)
        if num_nodes > DENSE_PAGERANK_MAX_NODES:
            _pagerank_segment(es, es, lv, plane, pr0, 0.85, 1e-6, num_nodes,
                              PAGERANK_MAX_ITERS)
    if num_nodes <= DENSE_PAGERANK_MAX_NODES:
        _pagerank_dense(np.zeros((num_nodes, num_nodes), np.float32),
                        pr0, pr0, 0.85, 1e-6, PAGERANK_MAX_ITERS)
        return len(lengths) + 1
    return 2 * len(lengths)


def cc_warm_labels(prev_labels: np.ndarray, node_mask: np.ndarray,
                   quad_nodes: tuple[np.ndarray, np.ndarray],
                   quad_edges: tuple[np.ndarray, np.ndarray],
                   edge_src: np.ndarray, edge_dst: np.ndarray) -> np.ndarray:
    """Incremental starting labels for :func:`connected_components_fixpoint`.

    Only *affected* components are re-unioned: components that lost an edge
    or a node are reset to per-node singleton labels (a deletion may have
    split them, and their old minimum id may even be the deleted node's);
    components touched solely by additions keep their labels — added edges
    are pre-merged with a host union-find so a merge costs O(1) flooding
    sweeps instead of O(diameter).  Untouched components keep their
    converged labels and contribute nothing to the remaining sweeps."""
    node_add, node_del = quad_nodes
    edge_add, edge_del = quad_edges
    big = np.iinfo(np.int32).max
    labels = np.where(node_mask, prev_labels.astype(np.int64), big).copy()

    # 1. reset components affected by deletions (splits) to singletons
    affected = set()
    for e in np.asarray(edge_del, np.int64):
        for end in (edge_src[e], edge_dst[e]):
            if prev_labels[end] != big:
                affected.add(int(prev_labels[end]))
    for s in np.asarray(node_del, np.int64):
        if prev_labels[s] != big:
            affected.add(int(prev_labels[s]))
    if affected:
        reset = np.isin(prev_labels, list(affected)) & node_mask
        labels[reset] = np.nonzero(reset)[0]

    # 2. new nodes start as singletons
    na = np.asarray(node_add, np.int64)
    na = na[na < labels.size]
    labels[na[node_mask[na]]] = na[node_mask[na]]

    # 3. pre-merge added edges with a tiny union-find over labels
    parent: dict[int, int] = {}

    def find(x: int) -> int:
        r = x
        while parent.get(r, r) != r:
            r = parent[r]
        while parent.get(x, x) != x:
            parent[x], x = r, parent[x]
        return r

    merged = False
    for e in np.asarray(edge_add, np.int64):
        u, v = int(edge_src[e]), int(edge_dst[e])
        if not (node_mask[u] and node_mask[v]):
            continue
        ra, rb = find(int(labels[u])), find(int(labels[v]))
        if ra != rb:
            parent[max(ra, rb)] = min(ra, rb)
            merged = True
    if merged:
        touched = np.fromiter(parent.keys(), np.int64)
        roots = np.array([find(int(t)) for t in touched], np.int64)
        remap = dict(zip(touched.tolist(), roots.tolist()))
        uniq, inv = np.unique(labels, return_inverse=True)
        uniq = np.array([remap.get(int(u), int(u)) for u in uniq], np.int64)
        labels = uniq[inv]

    labels = np.where(node_mask, labels, big)
    return np.clip(labels, None, big).astype(np.int32)


def incremental_degrees(deg: np.ndarray, edge_add: np.ndarray,
                        edge_del: np.ndarray, edge_src: np.ndarray,
                        edge_dst: np.ndarray) -> np.ndarray:
    """Advance a dense degree vector by a net inter-snapshot edge delta
    (``edge_add``/``edge_del`` are *net* slot sets — an edge added and
    deleted inside the slice appears in neither).  O(|delta|), matching
    :func:`degrees_masked`'s convention (live edges count both endpoints,
    node mask not consulted)."""
    out = deg.copy()
    for slots, sign in ((np.asarray(edge_add, np.int64), 1),
                       (np.asarray(edge_del, np.int64), -1)):
        if slots.size:
            np.add.at(out, edge_src[slots], sign)
            np.add.at(out, edge_dst[slots], sign)
    return out
