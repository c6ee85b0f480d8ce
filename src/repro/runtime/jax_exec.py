"""TPU-native snapshot retrieval: DeltaGraph plans on packed bitmaps.

The host planner (Dijkstra / Steiner on the skeleton) stays as-is; this
module replaces the *apply* phase with JAX:

1. every plan step — delta edge (either direction) or partial eventlist —
   collapses to one ``(adds, dels)`` bitmap pair (exact because element ids
   are never reused, §3.1, so membership toggles at most add→del once);
2. a singlepoint plan is therefore a K-step chain, executed by the fused
   ``delta_apply`` kernel in **one pass** over the bitmap (K+2 instead of
   3K words of HBM traffic);
3. the distributed engine lays bitmap words out ``[P, Wp]`` per the
   ``word_cyclic`` partitioner and runs the same chain under ``shard_map``
   — per-partition deltas touch only their own words, so the lowered HLO
   contains **zero collectives** (the paper's "no network communication
   among machines during retrieval", made checkable: see
   ``tests/test_distributed.py``).
"""
from __future__ import annotations

import functools
from typing import Any

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .spans import span
from .staging import DeviceStager, stream_chunk_k, to_device, to_host
from ..core import bitmaps as bmod
from ..core import planir
from ..core.deltagraph import DeltaGraph, Plan
from ..core.events import (EV_DEL_EDGE, EV_DEL_NODE, EV_NEW_EDGE, EV_NEW_NODE)
from ..core.query import NO_ATTRS
from ..kernels import (FusedOut, bucket_edges, delta_apply_chain,
                       delta_apply_chain_batched,
                       delta_apply_chain_prefix_batched, delta_apply_fused,
                       policy, segment_sum)
from ..storage import columnar as col


# ---------------------------------------------------------------------------
# plan → (adds, dels) index pairs
# ---------------------------------------------------------------------------

_fit_words = bmod.np_fit_words

def _elist_pair(comps, forward: bool, rng) -> tuple[np.ndarray, ...]:
    s = comps[col.ELIST_STRUCT]
    t = s["time"]
    m = np.ones(t.shape, bool) if rng is None else (t > rng[0]) & (t <= rng[1])
    et, sl = s["etype"][m], s["slot"][m]

    def pair(new_code, del_code):
        new_s = sl[et == new_code]
        del_s = sl[et == del_code]
        if forward:
            adds = np.setdiff1d(new_s, del_s)   # add-then-del nets to del
            dels = del_s
        else:
            adds = np.setdiff1d(del_s, new_s)   # un-delete revives
            dels = new_s
        return adds.astype(np.int32), dels.astype(np.int32)

    na, nd = pair(EV_NEW_NODE, EV_DEL_NODE)
    ea, ed = pair(EV_NEW_EDGE, EV_DEL_EDGE)
    return na, nd, ea, ed


def _recent_pair(dg: DeltaGraph, forward: bool, rng) -> tuple[np.ndarray, ...]:
    ev = dg.recent
    t = ev.time
    m = np.ones(t.shape, bool) if rng is None else (t > rng[0]) & (t <= rng[1])
    et, sl = ev.etype[m], ev.slot[m]

    def pair(new_code, del_code):
        new_s = sl[et == new_code]
        del_s = sl[et == del_code]
        if forward:
            return (np.setdiff1d(new_s, del_s).astype(np.int32),
                    del_s.astype(np.int32))
        return (np.setdiff1d(del_s, new_s).astype(np.int32),
                new_s.astype(np.int32))

    na, nd = pair(EV_NEW_NODE, EV_DEL_NODE)
    ea, ed = pair(EV_NEW_EDGE, EV_DEL_EDGE)
    return na, nd, ea, ed


def plan_to_chain(dg: DeltaGraph, plan: Plan, pool=None
                  ) -> tuple[tuple[np.ndarray, np.ndarray], list[tuple]]:
    """Lower a *singlepoint* plan into (base bitmaps, [(na,nd,ea,ed), ...])."""
    assert len(plan.targets) == 1, "use per-branch lowering for multipoint"
    steps = plan.steps
    src = steps[0]
    U_n, U_e = dg.universe.num_nodes, dg.universe.num_edges
    if src.action[0] == "empty":
        base_n = np.zeros(bmod.num_words(U_n), np.uint32)
        base_e = np.zeros(bmod.num_words(U_e), np.uint32)
    elif src.action[0] == "mat":
        base_n, base_e = pool._resolve_masks(src.action[1])
        base_n = _fit_words(base_n, bmod.num_words(U_n))
        base_e = _fit_words(base_e, bmod.num_words(U_e))
    elif src.action[0] == "current":
        st = dg._last_leaf_state.resized(dg.universe)
        base_n = bmod.np_pack(st.node_mask)
        base_e = bmod.np_pack(st.edge_mask)
        na, nd, ea, ed = _recent_pair(dg, True, None)
        chain0 = [(na, nd, ea, ed)]
    else:  # pragma: no cover
        raise ValueError(src.action)
    chain: list[tuple] = [] if src.action[0] != "current" else chain0
    for st in steps[1:]:
        kind = st.action[0]
        if kind == "delta":
            d = dg._fetch_delta(st.action[1], NO_ATTRS)
            if st.action[2]:
                chain.append((d.node_add, d.node_del, d.edge_add, d.edge_del))
            else:
                chain.append((d.node_del, d.node_add, d.edge_del, d.edge_add))
        elif kind == "elist":
            comps = dg._fetch_elist(st.action[1], NO_ATTRS)
            chain.append(_elist_pair(comps, st.action[2], st.action[3]))
        elif kind == "recent":
            chain.append(_recent_pair(dg, st.action[2], st.action[3]))
        elif kind == "noop":
            pass
        else:  # pragma: no cover
            raise ValueError(st.action)
    return (base_n, base_e), chain


# ---------------------------------------------------------------------------
# single-device execution (fused kernel)
# ---------------------------------------------------------------------------

def _stack_bitmaps(chain_idx: list[np.ndarray], U: int) -> jnp.ndarray:
    W = bmod.num_words(U)
    if not chain_idx:
        return jnp.zeros((0, W), jnp.uint32)
    with span("host.pack"):
        rows = np.stack([bmod.np_from_indices(ix, U) for ix in chain_idx])
    return to_device(rows)


def execute_singlepoint_jax(dg: DeltaGraph, t: int, *, impl: str | None = None,
                            pool=None, use_current: bool = True
                            ) -> tuple[np.ndarray, np.ndarray]:
    """Returns (node_mask, edge_mask) bool arrays, computed on-device."""
    plan = dg.plan_singlepoint(t, NO_ATTRS, use_current)
    (base_n, base_e), chain = plan_to_chain(dg, plan, pool)
    U_n, U_e = dg.universe.num_nodes, dg.universe.num_edges
    n_adds = _stack_bitmaps([c[0] for c in chain], U_n)
    n_dels = _stack_bitmaps([c[1] for c in chain], U_n)
    e_adds = _stack_bitmaps([c[2] for c in chain], U_e)
    e_dels = _stack_bitmaps([c[3] for c in chain], U_e)
    out_n = delta_apply_chain(jnp.asarray(base_n), n_adds, n_dels, impl=impl)
    out_e = delta_apply_chain(jnp.asarray(base_e), e_adds, e_dels, impl=impl)
    nm = bmod.np_unpack(np.asarray(out_n), U_n)
    em = bmod.np_unpack(np.asarray(out_e), U_e)
    em &= ~dg.universe.edge_transient[:U_e]
    nm &= ~dg.universe.node_transient[:U_n]
    return nm, em


# ---------------------------------------------------------------------------
# fused retrieval + analytics (single pass over the landed bitmaps)
# ---------------------------------------------------------------------------


def edge_buckets(uni, block_n: int = 128) -> tuple[tuple, tuple]:
    """``segment_sum``'s Pallas bucket tables for the universe's edge
    sources and destinations, as its ``buckets=`` takes them, on the device.

    The tables are a pure function of the edge endpoints, ``num_nodes`` and
    ``block_n``, and the universe only appends, so they are built once per
    universe shape (one ``kernel.bucket`` span a side) and kept in the
    universe's memo, which the next append clears.  ``block_n`` must be
    the one the ``segment_sum`` calls pass.
    """
    N, E = uni.num_nodes, uni.num_edges

    def build():
        tables = []
        for ids in (uni.edge_src[:E], uni.edge_dst[:E]):
            with span("kernel.bucket"):
                order, local, ME = bucket_edges(ids, N, block_n)
                tables.append((to_device(order.reshape(-1)),
                               to_device(local), ME))
        return tuple(tables)

    return uni.memo(("segment_sum.buckets", N, E, block_n), build)


class SnapshotAnalytics:
    """Push-style analytics emitted by the fused delta-apply kernel: the
    node/edge :class:`FusedOut` partials from the same pass that landed the
    chain.  ``node.live_count()`` / ``edge.live_count()`` are the snapshot
    order and size; ``edge.live`` feeds :func:`degrees` (per-node degree via
    the segment_sum kernel); ``node.weighted_total()`` is the PageRank push
    mass when per-slot contributions were supplied."""

    def __init__(self, node: FusedOut, edge: FusedOut, dg: DeltaGraph):
        self.node = node
        self.edge = edge
        self._dg = dg

    def num_nodes(self) -> int:
        return int(self.node.live_count())

    def num_edges(self) -> int:
        return int(self.edge.live_count())

    def degrees(self, *, impl: str | None = None) -> np.ndarray:
        """Per-node degree (both endpoints of live edges) reduced from the
        fused kernel's unpacked edge indicator by the segment_sum kernel —
        no host round-trip between apply and reduction."""
        uni = self._dg.universe
        E, N = uni.num_edges, uni.num_nodes
        live = self.edge.live[:E][:, None]
        src, dst = uni.edge_src[:E], uni.edge_dst[:E]
        if policy.resolve(impl)[0] == "pallas":
            b_src, b_dst = edge_buckets(uni)
        else:
            src, dst = jnp.asarray(src), jnp.asarray(dst)
            b_src = b_dst = None
        deg = (segment_sum(live, src, N, impl=impl, buckets=b_src)
               + segment_sum(live, dst, N, impl=impl, buckets=b_dst))
        return np.asarray(deg).reshape(-1)


def _transient_step(dg: DeltaGraph, U_n: int, U_e: int):
    """Transient slots cleared as one more chain step (zero adds, packed
    transient dels) — fused analytics then see exactly the returned masks."""
    return (bmod.np_pack(dg.universe.node_transient[:U_n]),
            bmod.np_pack(dg.universe.edge_transient[:U_e]))


def execute_singlepoint_fused(dg: DeltaGraph, t: int, *,
                              node_weights=None, impl: str | None = None,
                              pool=None, use_current: bool = True
                              ) -> tuple[np.ndarray, np.ndarray,
                                         SnapshotAnalytics]:
    """Single-point retrieval with analytics fused into the apply pass.

    Same plan and chain lowering as :func:`execute_singlepoint_jax`, but
    executed by the fused kernel: while each bitmap block holds the landed
    chain state in registers it also emits popcount/degree partials and
    (optionally, via ``node_weights [num_nodes] f32``) a PageRank-style
    push accumulator — the separate analytics sweep over the mask is gone.
    Transient-slot clearing folds into the chain as a final delete step, so
    analytics and the returned bool masks agree bit-for-bit.
    """
    plan = dg.plan_singlepoint(t, NO_ATTRS, use_current)
    (base_n, base_e), chain = plan_to_chain(dg, plan, pool)
    U_n, U_e = dg.universe.num_nodes, dg.universe.num_edges
    W_n, W_e = bmod.num_words(U_n), bmod.num_words(U_e)
    tn, te = _transient_step(dg, U_n, U_e)
    n_adds = np.stack([bmod.np_from_indices(c[0], U_n) for c in chain]
                      + [np.zeros(W_n, np.uint32)])
    n_dels = np.stack([bmod.np_from_indices(c[1], U_n) for c in chain] + [tn])
    e_adds = np.stack([bmod.np_from_indices(c[2], U_e) for c in chain]
                      + [np.zeros(W_e, np.uint32)])
    e_dels = np.stack([bmod.np_from_indices(c[3], U_e) for c in chain] + [te])
    w = None
    if node_weights is not None:
        w = jnp.asarray(np.asarray(node_weights, np.float32).reshape(-1))
    fn = delta_apply_fused(jnp.asarray(base_n), jnp.asarray(n_adds),
                           jnp.asarray(n_dels), w, impl=impl)
    fe = delta_apply_fused(jnp.asarray(base_e), jnp.asarray(e_adds),
                           jnp.asarray(e_dels), impl=impl)
    nm = bmod.np_unpack(np.asarray(fn.mask), U_n)
    em = bmod.np_unpack(np.asarray(fe.mask), U_e)
    return nm, em, SnapshotAnalytics(fn, fe, dg)


# ---------------------------------------------------------------------------
# IR DAG execution: vmapped multi-snapshot apply
# ---------------------------------------------------------------------------

_EMPTY_PAIR = (np.zeros(0, np.int32),) * 4


def _node_pair(dg: DeltaGraph, op, get_payload) -> tuple[np.ndarray, ...]:
    """Lower one apply op to an ``(n_add, n_del, e_add, e_del)`` index
    quadruple; payloads come through ``get_payload`` (memoized per pid,
    possibly prefetched)."""
    if isinstance(op, planir.ApplyDelta):
        d = get_payload("delta", op.pid)
        if op.forward:
            return d.node_add, d.node_del, d.edge_add, d.edge_del
        return d.node_del, d.node_add, d.edge_del, d.edge_add
    if isinstance(op, planir.ApplyElist):
        return _elist_pair(get_payload("elist", op.pid), op.forward, op.rng)
    if isinstance(op, planir.ApplyRecent):
        return _recent_pair(dg, op.forward, op.rng)
    if isinstance(op, planir.Noop):
        return _EMPTY_PAIR
    raise ValueError(f"not an apply op: {op}")  # pragma: no cover


def _make_payload_resolver(dg: DeltaGraph, ir: Plan, prefetch):
    """Memoized payload access for the structure-only backend; with a
    Prefetcher, every Fetch node's (small, struct-component) key list is
    submitted up front — the worker threads fetch *and decode* the blobs,
    so store gets and codec decompression both overlap kernel execution
    and the host-fetch path consumes ready arrays."""
    futs: dict[tuple, Any] = {}
    if prefetch is not None:
        for n in ir.nodes:
            if not isinstance(n.op, planir.Fetch):
                continue
            fk = (n.op.kind, n.op.pid)
            if fk in futs:
                continue
            if n.op.kind == "delta":
                keys, na, ea = dg._delta_keys(n.op.pid, NO_ATTRS)
                allk, meta = keys + na + ea, (len(keys), len(na))
                decode = (lambda blobs, meta=meta:
                          dg._decode_delta(blobs, *meta))
            else:
                allk = dg._elist_keys(n.op.pid, NO_ATTRS)
                decode = (lambda blobs, allk=allk:
                          dg._decode_elist(allk, blobs))
            futs[fk] = prefetch.submit(allk, decode=decode)
    payloads: dict[tuple, Any] = {}

    def get_payload(kind: str, pid: int):
        fk = (kind, pid)
        if fk not in payloads:
            fut = futs.pop(fk, None)
            with span("kv.wait"):
                if fut is not None:
                    payloads[fk] = fut.result()   # decoded in the worker
                else:
                    payloads[fk] = (dg._fetch_delta(pid, NO_ATTRS)
                                    if kind == "delta"
                                    else dg._fetch_elist(pid, NO_ATTRS))
        return payloads[fk]

    return get_payload


def _np_apply_pair(bn: np.ndarray, be: np.ndarray, pair, U_n: int, U_e: int):
    na, nd, ea, ed = pair
    bn = (bn & ~bmod.np_from_indices(nd, U_n)) | bmod.np_from_indices(na, U_n)
    be = (be & ~bmod.np_from_indices(ed, U_e)) | bmod.np_from_indices(ea, U_e)
    return bn, be


def _apply_chains_streamed(bases_n, bases_e, chains, U_n: int, U_e: int, *,
                           impl, prefetch=None, stager: DeviceStager | None
                           = None) -> tuple[np.ndarray, np.ndarray]:
    """Land B index-quad chains over the node+edge planes, double-buffered.

    ``chains[i]`` is a list of ``(na, nd, ea, ed)`` slot-index quads.  When
    the common chain length exceeds the stream chunk
    (``REPRO_STREAM_CHUNK``, default 8) the ``[B, K, W]`` plane stacks are
    never materialized whole: the :class:`DeviceStager` builds (codec
    indices → packed planes) and ``device_put``s chunk *i+1* while chunk
    *i*'s kernels run.  The chain is a left fold of bitwise steps, so the
    chunked landing is bit-identical to the monolithic call."""
    W_n, W_e = bmod.num_words(U_n), bmod.num_words(U_e)
    B = len(chains)
    K = max(len(c) for c in chains)
    if K == 0:
        return np.asarray(bases_n), np.asarray(bases_e)

    def build(lo: int, hi: int):
        k = hi - lo
        an = np.zeros((B, k, W_n), np.uint32)
        dn = np.zeros((B, k, W_n), np.uint32)
        ae = np.zeros((B, k, W_e), np.uint32)
        de = np.zeros((B, k, W_e), np.uint32)
        with span("host.pack"):
            for i, chain in enumerate(chains):
                for j in range(lo, min(hi, len(chain))):
                    na, nd, ea, ed = chain[j]
                    an[i, j - lo] = bmod.np_from_indices(na, U_n)
                    dn[i, j - lo] = bmod.np_from_indices(nd, U_n)
                    ae[i, j - lo] = bmod.np_from_indices(ea, U_e)
                    de[i, j - lo] = bmod.np_from_indices(ed, U_e)
        return an, dn, ae, de

    ck = stream_chunk_k()
    if ck < 1 or K <= ck:
        an, dn, ae, de = build(0, K)
        out_n = delta_apply_chain_batched(
            to_device(bases_n), to_device(an), to_device(dn), impl=impl)
        out_e = delta_apply_chain_batched(
            to_device(bases_e), to_device(ae), to_device(de), impl=impl)
        return to_host(out_n), to_host(out_e)

    if stager is None:
        stager = DeviceStager(prefetcher=prefetch)
    nch = -(-K // ck)

    def apply_chunk(carry, dev):
        bn, be = carry
        an, dn, ae, de = dev
        return (delta_apply_chain_batched(bn, an, dn, impl=impl),
                delta_apply_chain_batched(be, ae, de, impl=impl))

    bn, be = stager.stream(
        nch, lambda i: build(i * ck, min((i + 1) * ck, K)), apply_chunk,
        (to_device(bases_n), to_device(bases_e)))
    return to_host(bn), to_host(be)


def execute_ir_jax(dg: DeltaGraph, ir: Plan, *, impl: str | None = None,
                   pool=None, prefetch=None,
                   stager: DeviceStager | None = None
                   ) -> dict[Any, tuple[np.ndarray, np.ndarray]]:
    """Execute a plan IR (structure-only) on the JAX bitmap backend.

    The DAG is decomposed into maximal linear **segments** between
    boundaries (sources, Fork nodes, targets); every wave batches all
    ready segments — sibling branches after a Fork in particular — into a
    single vmapped ``delta_apply_chain`` call over stacked bit-planes, so
    B branches cost one fused pass instead of B sequential chains.

    Returns ``{target: (node_mask, edge_mask)}`` bool arrays.
    """
    U_n, U_e = dg.universe.num_nodes, dg.universe.num_edges
    W_n, W_e = bmod.num_words(U_n), bmod.num_words(U_e)
    byid = {n.nid: n for n in ir.nodes}
    get_payload = _make_payload_resolver(dg, ir, prefetch)

    # state topology: apply children per state node; forks pass through
    children: dict[int, list[int]] = {}
    fork_child: dict[int, int] = {}
    for n in ir.nodes:
        if isinstance(n.op, planir.APPLY_OPS):
            for d in n.deps:
                if not isinstance(byid[d].op, planir.Fetch):
                    children.setdefault(d, []).append(n.nid)
        elif isinstance(n.op, planir.Fork):
            fork_child[n.deps[0]] = n.nid

    target_nids = set(ir.targets.values())

    def is_boundary(nid: int) -> bool:
        return (nid in target_nids or nid in fork_child
                or len(children.get(nid, ())) != 1)

    # source values (host-side: tiny — one packed bitmap each)
    vals: dict[int, tuple[np.ndarray, np.ndarray]] = {}
    frontier: list[int] = []
    for n in ir.nodes:
        op = n.op
        if isinstance(op, planir.Source):
            if op.kind == "empty":
                v = (np.zeros(W_n, np.uint32), np.zeros(W_e, np.uint32))
            elif op.kind == "mat":
                assert pool is not None, "materialized plan needs a GraphPool"
                pn, pe = pool._resolve_masks(op.gid)
                v = (_fit_words(pn, W_n), _fit_words(pe, W_e))
            else:  # current = last leaf + recent events
                st = dg._last_leaf_state.resized(dg.universe)
                v = _np_apply_pair(bmod.np_pack(st.node_mask),
                                   bmod.np_pack(st.edge_mask),
                                   _recent_pair(dg, True, None), U_n, U_e)
            vals[n.nid] = v
            frontier.append(n.nid)

    def expand(nid: int) -> None:
        """Fork nodes inherit their parent's value and join the frontier."""
        if nid in fork_child:
            f = fork_child[nid]
            vals[f] = vals[nid]
            frontier.append(f)

    for nid in list(vals):
        expand(nid)

    while frontier:
        # collect every ready segment in this wave
        segments: list[tuple[int, list[int]]] = []   # (parent, [apply nids])
        wave, frontier = frontier, []
        for pnid in wave:
            for c in children.get(pnid, ()):
                seg = [c]
                while not is_boundary(seg[-1]):
                    seg.append(children[seg[-1]][0])
                segments.append((pnid, seg))
        if not segments:
            break
        with span("slice.quads"):
            chains = [[_node_pair(dg, byid[s].op, get_payload) for s in seg]
                      for _, seg in segments]
        bases_n = np.stack([vals[p][0] for p, _ in segments])
        bases_e = np.stack([vals[p][1] for p, _ in segments])
        out_n, out_e = _apply_chains_streamed(
            bases_n, bases_e, chains, U_n, U_e, impl=impl,
            prefetch=prefetch, stager=stager)
        for i, (_, seg) in enumerate(segments):
            end = seg[-1]
            vals[end] = (out_n[i], out_e[i])
            frontier.append(end)
            expand(end)

    out: dict[Any, tuple[np.ndarray, np.ndarray]] = {}
    with span("host.unpack"):
        for tgt, nid in ir.targets.items():
            nm = bmod.np_unpack(vals[nid][0], U_n)
            em = bmod.np_unpack(vals[nid][1], U_e)
            nm &= ~dg.universe.node_transient[:U_n]
            em &= ~dg.universe.edge_transient[:U_e]
            out[tgt] = (nm, em)
    return out


def execute_multipoint_jax(dg: DeltaGraph, times, *, impl: str | None = None,
                           pool=None, use_current: bool = True,
                           land_in_pool: bool = False, prefetch=None):
    """Batched multipoint retrieval on the JAX backend: one Steiner plan,
    sibling branches vmapped, store gets optionally prefetched.  Returns
    ``{t: (node_mask, edge_mask)}``, or ``{t: pool gid}`` when
    ``land_in_pool`` — the masks are then overlaid into GraphPool bit
    pairs in a single batched insert."""
    ir = dg.plan_multipoint([int(t) for t in times], NO_ATTRS, use_current)
    masks = execute_ir_jax(dg, ir, impl=impl, pool=pool, prefetch=prefetch)
    if not land_in_pool:
        return masks
    assert pool is not None, "land_in_pool needs a GraphPool"
    order = list(masks)
    gids = pool.insert_snapshots_packed(
        [(bmod.np_pack(masks[t][0]), bmod.np_pack(masks[t][1]))
         for t in order])
    return dict(zip(order, gids))


# ---------------------------------------------------------------------------
# vmapped multi-interval temporal analytics
# ---------------------------------------------------------------------------

def evolve_intervals_jax(dg: DeltaGraph, intervals, *, impl: str | None = None,
                         pool=None, use_current: bool = True, prefetch=None,
                         stager: DeviceStager | None = None
                         ) -> list[dict[int, tuple[np.ndarray, np.ndarray]]]:
    """Per-timepoint (node_mask, edge_mask) for **B intervals at once**.

    The B interval *start* snapshots retrieve as one Steiner plan on the
    batched IR backend (:func:`execute_ir_jax` — sibling branches run as a
    single ``delta_apply_chain_batched`` call); the starts then become the
    base planes of a ``[B, K-1, W]`` stack of inter-snapshot delta bitmaps
    (net event slices via :mod:`repro.core.temporal`, each covering leaf
    eventlist fetched once per call) swept by the vmapped prefix chain —
    every prefix **is** one interval timepoint's membership bitmap, ready
    to feed the vmapped plane-masked analytics
    (:func:`repro.graph.algorithms.multi_snapshot_pagerank` etc.).

    Returns one ``{t: (node_mask, edge_mask)}`` dict per interval,
    bit-identical to the host engine (``tests/test_differential_exec.py``).
    """
    from ..core.temporal import IntervalSlicer
    ivs = [sorted(dict.fromkeys(int(t) for t in iv)) for iv in intervals]
    if not ivs or any(not iv for iv in ivs):
        raise ValueError("every interval needs at least one timepoint")
    U_n, U_e = dg.universe.num_nodes, dg.universe.num_edges
    W_n, W_e = bmod.num_words(U_n), bmod.num_words(U_e)

    # 1. batched retrieval of the B start snapshots (deduped by the plan)
    ir = dg.plan_multipoint([iv[0] for iv in ivs], NO_ATTRS, use_current)
    start_masks = execute_ir_jax(dg, ir, impl=impl, pool=pool,
                                 prefetch=prefetch)

    # 2. one slicer for the whole batch: overlapping intervals share leaf
    #    eventlist fetches, and quads are exactly the temporal engine's
    slicer = IntervalSlicer(dg, NO_ATTRS, prefetcher=prefetch)
    for iv in ivs:
        slicer.prefetch_interval(iv[0], iv[-1])
    with span("slice.quads"):
        quads = [[slicer.quad(lo, hi) for lo, hi in zip(iv, iv[1:])]
                 for iv in ivs]

    # 3. vmapped prefix sweep (zero-padded rows are identity steps)
    B = len(ivs)
    Kmax = max(len(q) for q in quads)
    out: list[dict[int, tuple[np.ndarray, np.ndarray]]] = [
        {iv[0]: start_masks[iv[0]]} for iv in ivs]
    if Kmax == 0:
        return out
    with span("host.pack"):
        bases_n = np.stack([bmod.np_pack(start_masks[iv[0]][0])
                            for iv in ivs])
        bases_e = np.stack([bmod.np_pack(start_masks[iv[0]][1])
                            for iv in ivs])

    def build(lo: int, hi: int):
        k = hi - lo
        an = np.zeros((B, k, W_n), np.uint32)
        dn = np.zeros((B, k, W_n), np.uint32)
        ae = np.zeros((B, k, W_e), np.uint32)
        de = np.zeros((B, k, W_e), np.uint32)
        with span("host.pack"):
            for b, qs in enumerate(quads):
                for j in range(lo, min(hi, len(qs))):
                    q = qs[j]
                    an[b, j - lo] = bmod.np_from_indices(q.node_add, U_n)
                    dn[b, j - lo] = bmod.np_from_indices(q.node_del, U_n)
                    ae[b, j - lo] = bmod.np_from_indices(q.edge_add, U_e)
                    de[b, j - lo] = bmod.np_from_indices(q.edge_del, U_e)
        return an, dn, ae, de

    ck = stream_chunk_k()
    if ck < 1 or Kmax <= ck:
        an, dn, ae, de = build(0, Kmax)
        pref_n = to_host(delta_apply_chain_prefix_batched(
            to_device(bases_n), to_device(an), to_device(dn)))
        pref_e = to_host(delta_apply_chain_prefix_batched(
            to_device(bases_e), to_device(ae), to_device(de)))
    else:
        # streamed prefix sweep: each chunk's last prefix seeds the next
        # chunk's base, so chunked prefixes concatenate bit-identically
        if stager is None:
            stager = DeviceStager(prefetcher=prefetch)
        nch = -(-Kmax // ck)
        parts: list[tuple] = []

        def apply_chunk(carry, dev):
            bn, be = carry
            an, dn, ae, de = dev
            pn = delta_apply_chain_prefix_batched(bn, an, dn)
            pe = delta_apply_chain_prefix_batched(be, ae, de)
            parts.append((pn, pe))
            return pn[:, -1], pe[:, -1]

        stager.stream(nch, lambda i: build(i * ck, min((i + 1) * ck, Kmax)),
                      apply_chunk,
                      (to_device(bases_n), to_device(bases_e)))
        pref_n = np.concatenate([to_host(p[0]) for p in parts], axis=1)
        pref_e = np.concatenate([to_host(p[1]) for p in parts], axis=1)
    with span("host.unpack"):
        for b, iv in enumerate(ivs):
            for j, t in enumerate(iv[1:]):
                nm = bmod.np_unpack(pref_n[b, j], U_n)
                em = bmod.np_unpack(pref_e[b, j], U_e)
                nm &= ~dg.universe.node_transient[:U_n]
                em &= ~dg.universe.edge_transient[:U_e]
                out[b][t] = (nm, em)
    return out


# ---------------------------------------------------------------------------
# distributed execution: shard_map over the node-ID partitions
# ---------------------------------------------------------------------------

def _to_sharded_layout(idx: np.ndarray, U: int, Pn: int) -> np.ndarray:
    """Slot → (partition row, local bit) under word_cyclic: word w lives at
    row ``w % P``, column ``w // P``; the local flat bit index is
    ``(w // P) * 32 + (slot & 31)``."""
    w = idx >> 5
    return (w % Pn).astype(np.int64), ((w // Pn) * 32 + (idx & 31)).astype(np.int64)


def _stack_sharded(chain_idx: list[np.ndarray], U: int, Pn: int) -> np.ndarray:
    Wp = -(-bmod.num_words(U) // Pn)
    K = len(chain_idx)
    out = np.zeros((K, Pn, Wp), np.uint32)
    for i, ix in enumerate(chain_idx):
        ix = np.asarray(ix, np.int64)
        if ix.size == 0:
            continue
        row, lbit = _to_sharded_layout(ix, U, Pn)
        np.bitwise_or.at(out[i], (row, lbit >> 5),
                         np.uint32(1) << (lbit & 31).astype(np.uint32))
    return out


def sharded_base(words: np.ndarray, Pn: int) -> np.ndarray:
    """Re-lay a packed bitmap [W] into the [P, Wp] word-cyclic layout."""
    W = words.size
    Wp = -(-W // Pn)
    out = np.zeros((Pn, Wp), np.uint32)
    w = np.arange(W)
    out[w % Pn, w // Pn] = words
    return out


def unshard(words_pw: np.ndarray, W: int) -> np.ndarray:
    Pn, Wp = words_pw.shape
    out = np.zeros(Pn * Wp, np.uint32)
    w = np.arange(W)
    out[:W] = words_pw[w % Pn, w // Pn]
    return out[:W]


def _scatter_row(out_kp: np.ndarray, ix: np.ndarray, Pn: int) -> None:
    """OR slot indices into one partition row [Wp] of the word_cyclic
    layout (the caller guarantees every slot belongs to that row)."""
    ix = np.asarray(ix, np.int64)
    if ix.size == 0:
        return
    lbit = ((ix >> 5) // Pn) * 32 + (ix & 31)
    np.bitwise_or.at(out_kp, lbit >> 5,
                     np.uint32(1) << (lbit & 31).astype(np.uint32))


_EMPTY_PAIR = (np.zeros(0, np.int32),) * 4


def plan_to_chain_sharded(dg: DeltaGraph, plan: Plan, Pn: int, pool=None
                          ) -> tuple[tuple[np.ndarray, np.ndarray],
                                     tuple[np.ndarray, ...]]:
    """Lower a *singlepoint* plan into base bitmaps plus per-partition
    ``[K, P, Wp]`` add/del stacks, fetching each storage partition's
    sub-payloads **separately** — the fetch pattern of the aligned
    deployment, where device ``p`` pulls only the partition-``p`` keys
    from the store and fills exactly its own layout row.

    Requires ``dg.P == Pn`` under the ``word_cyclic`` partitioner, so a
    delta/eventlist sub-payload's slots land entirely in row ``p``.
    In-memory steps (recent events, which are not yet partitioned into
    storage) carry slots from every partition and are scattered across
    rows like the dense path does."""
    assert len(plan.targets) == 1, "use per-branch lowering for multipoint"
    if dg.P != Pn or dg.partition_fn_name != "word_cyclic":
        raise ValueError(
            f"aligned sharded lowering needs dg.P == {Pn} storage "
            f"partitions under word_cyclic; have P={dg.P} "
            f"fn={dg.partition_fn_name}")
    steps = plan.steps
    src = steps[0]
    U_n, U_e = dg.universe.num_nodes, dg.universe.num_edges
    entries: list[tuple[str, Any]] = []
    if src.action[0] == "empty":
        base_n = np.zeros(bmod.num_words(U_n), np.uint32)
        base_e = np.zeros(bmod.num_words(U_e), np.uint32)
    elif src.action[0] == "mat":
        base_n, base_e = pool._resolve_masks(src.action[1])
        base_n = _fit_words(base_n, bmod.num_words(U_n))
        base_e = _fit_words(base_e, bmod.num_words(U_e))
    elif src.action[0] == "current":
        st = dg._last_leaf_state.resized(dg.universe)
        base_n = bmod.np_pack(st.node_mask)
        base_e = bmod.np_pack(st.edge_mask)
        entries.append(("full", _recent_pair(dg, True, None)))
    else:  # pragma: no cover
        raise ValueError(src.action)
    for st in steps[1:]:
        kind = st.action[0]
        if kind == "delta":
            per = []
            for p in range(Pn):
                d = dg._fetch_delta(st.action[1], NO_ATTRS, parts=(p,))
                if st.action[2]:
                    per.append((d.node_add, d.node_del,
                                d.edge_add, d.edge_del))
                else:
                    per.append((d.node_del, d.node_add,
                                d.edge_del, d.edge_add))
            entries.append(("parts", per))
        elif kind == "elist":
            per = []
            for p in range(Pn):
                comps = dg._fetch_elist(st.action[1], NO_ATTRS,
                                        parts=(p,))
                per.append(_elist_pair(comps, st.action[2], st.action[3])
                           if col.ELIST_STRUCT in comps else _EMPTY_PAIR)
            entries.append(("parts", per))
        elif kind == "recent":
            entries.append(("full", _recent_pair(dg, st.action[2],
                                                 st.action[3])))
        elif kind == "noop":
            pass
        else:  # pragma: no cover
            raise ValueError(st.action)
    K = len(entries)
    Wp_n = -(-bmod.num_words(U_n) // Pn)
    Wp_e = -(-bmod.num_words(U_e) // Pn)
    stacks = (np.zeros((K, Pn, Wp_n), np.uint32),
              np.zeros((K, Pn, Wp_n), np.uint32),
              np.zeros((K, Pn, Wp_e), np.uint32),
              np.zeros((K, Pn, Wp_e), np.uint32))
    for k, (tag, data) in enumerate(entries):
        if tag == "parts":
            for p, pair in enumerate(data):
                for st_arr, ix in zip(stacks, pair):
                    _scatter_row(st_arr[k, p], ix, Pn)
        else:  # full-state step: slots span partitions
            for st_arr, ix in zip(stacks, data):
                ix = np.asarray(ix, np.int64)
                if ix.size == 0:
                    continue
                U = U_n if st_arr is stacks[0] or st_arr is stacks[1] else U_e
                row, lbit = _to_sharded_layout(ix, U, Pn)
                np.bitwise_or.at(
                    st_arr[k], (row, lbit >> 5),
                    np.uint32(1) << (lbit & 31).astype(np.uint32))
    return (base_n, base_e), stacks


@functools.lru_cache(maxsize=None)
def make_retrieval_fn(mesh: Mesh, axis: str = "data"):
    """Builds the shard_map'ed chain applier (one per mesh, so its compiled
    programs are reused).  Each device owns one row of the [P, Wp] layout;
    the chain is applied locally — no collectives."""

    def _local(base, adds, dels):
        def step(m, ad):
            a, d = ad
            return (m & ~d) | a, None
        out, _ = jax.lax.scan(step, base, (adds, dels))
        return out

    shard = jax.shard_map(
        _local, mesh=mesh,
        in_specs=(P(axis, None), P(None, axis, None), P(None, axis, None)),
        out_specs=P(axis, None))
    return jax.jit(shard)


def land_singlepoint_sharded(dg: DeltaGraph, t: int, mesh: Mesh, *,
                             axis: str = "data", pool=None,
                             use_current: bool = True
                             ) -> tuple[jax.Array, jax.Array]:
    """Land the node and edge planes of snapshot ``t`` in the ``[P, Wp]``
    word_cyclic layout, row ``p`` placed on (and applied by) the mesh's
    device ``p``.  Aligned fetches need ``dg.P == mesh.shape[axis]`` under
    the word_cyclic partitioner (storage partitions == compute partitions,
    the paper's aligned deployment); any other index is scattered into
    the layout on the host."""
    Pn = mesh.shape[axis]
    plan = dg.plan_singlepoint(t, NO_ATTRS, use_current)
    U_n, U_e = dg.universe.num_nodes, dg.universe.num_edges
    fn = make_retrieval_fn(mesh, axis)
    rows = NamedSharding(mesh, P(axis, None))
    chain_rows = NamedSharding(mesh, P(None, axis, None))
    aligned = dg.P == Pn and dg.partition_fn_name == "word_cyclic"
    if aligned:
        # aligned deployment: each partition's sub-payloads are fetched
        # separately and fill exactly their own layout row
        (base_n, base_e), (an, dn, ae, de) = plan_to_chain_sharded(
            dg, plan, Pn, pool)
        sides = ((base_n, an, dn, U_n), (base_e, ae, de, U_e))
    else:
        (base_n, base_e), chain = plan_to_chain(dg, plan, pool)
        sides = tuple(
            (base, _stack_sharded(ix_a, U, Pn), _stack_sharded(ix_d, U, Pn), U)
            for base, ix_a, ix_d, U in (
                (base_n, [c[0] for c in chain], [c[1] for c in chain], U_n),
                (base_e, [c[2] for c in chain], [c[3] for c in chain], U_e)))
    out_n, out_e = (
        fn(jax.device_put(sharded_base(np.asarray(base), Pn), rows),
           jax.device_put(adds, chain_rows), jax.device_put(dels, chain_rows))
        for base, adds, dels, _ in sides)
    return out_n, out_e


def sharded_masks(dg: DeltaGraph, out_n, out_e
                  ) -> tuple[np.ndarray, np.ndarray]:
    """``(node_mask, edge_mask)`` bool arrays from landed ``[P, Wp]``
    planes, transient slots cleared."""
    U_n, U_e = dg.universe.num_nodes, dg.universe.num_edges
    nm, em = (bmod.np_unpack(unshard(np.asarray(out), bmod.num_words(U)), U)
              for out, U in ((out_n, U_n), (out_e, U_e)))
    em &= ~dg.universe.edge_transient[:U_e]
    nm &= ~dg.universe.node_transient[:U_n]
    return nm, em


def execute_singlepoint_sharded(dg: DeltaGraph, t: int, mesh: Mesh, *,
                                axis: str = "data", pool=None,
                                use_current: bool = True
                                ) -> tuple[np.ndarray, np.ndarray]:
    """Distributed retrieval (:func:`land_singlepoint_sharded`), returned
    as host bool masks."""
    return sharded_masks(dg, *land_singlepoint_sharded(
        dg, t, mesh, axis=axis, pool=pool, use_current=use_current))


# HLO op names of cross-device collectives: none may appear in the
# sharded retrieval step's lowered text.
COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")


def lowered_retrieval_hlo(mesh: Mesh, K: int, Wp: int, axis: str = "data") -> str:
    """Lowered HLO text of the sharded retrieval step, for the assertion
    that it holds none of :data:`COLLECTIVES`."""
    Pn = mesh.shape[axis]
    fn = make_retrieval_fn(mesh, axis)
    args = (jax.ShapeDtypeStruct((Pn, Wp), jnp.uint32),
            jax.ShapeDtypeStruct((K, Pn, Wp), jnp.uint32),
            jax.ShapeDtypeStruct((K, Pn, Wp), jnp.uint32))
    return fn.lower(*args).compile().as_text()
