"""Graph analytics over bitmap planes vs networkx ground truth, the
Pregel API, the neighbor sampler, and the JAX retrieval engine."""
import networkx as nx
import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import GraphManager, bitmaps as bm, replay
from repro.data.generators import churn_network
from repro.graph.algorithms import (connected_components, degrees_masked,
                                    multi_snapshot_pagerank, pagerank,
                                    triangle_count)
from repro.graph.csr import build_csr
from repro.graph.pregel import run_pregel
from repro.graph.sampler import pad_blocks, sample_blocks, sampled_shapes
from repro.runtime.jax_exec import execute_singlepoint_jax


@pytest.fixture(scope="module")
def snap():
    uni, ev = churn_network(n_initial_edges=120, n_events=600, seed=11,
                            p_transient=0.0, p_attr_update=0.0)
    gm = GraphManager(uni, ev, L=64, k=2)
    t = int(ev.time[400])
    truth = replay(uni, ev, t)
    return uni, ev, gm, t, truth


def _nx_graph(uni, truth):
    g = nx.Graph()
    g.add_nodes_from(np.nonzero(truth.node_mask)[0].tolist())
    eid = np.nonzero(truth.edge_mask)[0]
    g.add_edges_from(zip(uni.edge_src[eid].tolist(),
                         uni.edge_dst[eid].tolist()))
    return g


def test_pagerank_matches_networkx(snap):
    uni, ev, gm, t, truth = snap
    ep = jnp.asarray(bm.np_pack(truth.edge_mask))
    np_ = jnp.asarray(bm.np_pack(truth.node_mask))
    pr = np.asarray(pagerank(jnp.asarray(uni.edge_src),
                             jnp.asarray(uni.edge_dst), ep, np_,
                             num_nodes=uni.num_nodes, iters=60))
    g = _nx_graph(uni, truth)
    g.remove_nodes_from(list(nx.isolates(g)))
    nxpr = nx.pagerank(g, alpha=0.85, max_iter=200)
    live = sorted(nxpr)
    mine = pr[live] / max(pr[live].sum(), 1e-12)
    ref = np.array([nxpr[n] for n in live])
    # ranking correlation is what matters for top-k analyses
    corr = np.corrcoef(mine, ref)[0, 1]
    assert corr > 0.99, corr


def test_degrees_and_cc(snap):
    uni, ev, gm, t, truth = snap
    ep = jnp.asarray(bm.np_pack(truth.edge_mask))
    npl = jnp.asarray(bm.np_pack(truth.node_mask))
    deg = np.asarray(degrees_masked(jnp.asarray(uni.edge_src),
                                    jnp.asarray(uni.edge_dst), ep,
                                    num_nodes=uni.num_nodes))
    exp = np.zeros(uni.num_nodes, np.int64)
    eid = np.nonzero(truth.edge_mask)[0]
    np.add.at(exp, uni.edge_src[eid], 1)
    np.add.at(exp, uni.edge_dst[eid], 1)
    assert np.array_equal(deg, exp)
    labels = np.asarray(connected_components(
        jnp.asarray(uni.edge_src), jnp.asarray(uni.edge_dst), ep, npl,
        num_nodes=uni.num_nodes, iters=100))
    g = _nx_graph(uni, truth)
    n_cc = nx.number_connected_components(g)
    live = np.nonzero(truth.node_mask)[0]
    assert len(set(labels[live].tolist())) == n_cc


def test_triangles(snap):
    uni, ev, gm, t, truth = snap
    mine = triangle_count(uni.edge_src, uni.edge_dst, truth.edge_mask,
                          uni.num_nodes)
    g = _nx_graph(uni, truth)
    exp = sum(nx.triangles(g).values()) // 3
    assert mine == exp


def test_multi_snapshot_vmap(snap):
    uni, ev, gm, t, truth = snap
    times = [int(ev.time[i]) for i in (100, 300, 500)]
    hs = gm.get_hist_graphs(times)
    nps, eps = gm.pool.stacked_planes([h.gid for h in hs])
    prs = np.asarray(multi_snapshot_pagerank(
        jnp.asarray(uni.edge_src), jnp.asarray(uni.edge_dst),
        jnp.asarray(eps), jnp.asarray(nps), num_nodes=uni.num_nodes,
        iters=20))
    assert prs.shape == (3, uni.num_nodes)
    assert np.all(np.isfinite(prs))


def test_pregel_degree(snap):
    uni, ev, gm, t, truth = snap
    ep = jnp.asarray(bm.np_pack(truth.edge_mask))
    state = jnp.zeros(uni.num_nodes, jnp.float32)

    def msg(src_state, dst_state, live):
        return live.astype(jnp.float32)

    def upd(state, agg, step):
        return agg

    out = np.asarray(run_pregel(state, jnp.asarray(uni.edge_src),
                                jnp.asarray(uni.edge_dst), ep, msg, upd,
                                num_supersteps=1, num_nodes=uni.num_nodes))
    exp = np.zeros(uni.num_nodes, np.float32)
    eid = np.nonzero(truth.edge_mask)[0]
    np.add.at(exp, uni.edge_dst[eid], 1)
    np.add.at(exp, uni.edge_src[eid], 1)
    assert np.array_equal(out, exp)


def test_neighbor_sampler(snap):
    uni, ev, gm, t, truth = snap
    csr = build_csr(uni.edge_src, uni.edge_dst, uni.num_nodes,
                    truth.edge_mask, uni.edge_directed)
    rng = np.random.default_rng(0)
    seeds = np.nonzero(truth.node_mask)[0][:8]
    b = sample_blocks(csr, seeds, [3, 2], rng)
    assert b.n_seeds == len(seeds)
    # every sampled edge is a real edge of the snapshot
    gsrc = b.nodes[b.edge_index[0]]
    gdst = b.nodes[b.edge_index[1]]
    for s, d, ok in zip(gsrc, gdst, b.edge_mask):
        if ok:
            assert d in csr.neighbors(int(s)) or s in csr.neighbors(int(d))
    n_pad, e_pad = sampled_shapes(8, [3, 2])
    pb = pad_blocks(b, n_pad, e_pad)
    assert pb.nodes.size == n_pad and pb.edge_index.shape[1] == e_pad


def test_jax_engine_matches_oracle(snap):
    uni, ev, gm, t, truth = snap
    for impl in ("xla", "pallas"):
        nm, em = execute_singlepoint_jax(gm.dg, t, impl=impl, pool=gm.pool)
        assert np.array_equal(nm, truth.node_mask)
        assert np.array_equal(em, truth.edge_mask)


# ---------------------------------------------------------------------------
# the served components solver against the union-find reference
# ---------------------------------------------------------------------------


def _path(n):
    return np.arange(n - 1), np.arange(1, n)


def _cc_graph(case):
    """``(num_nodes, src, dst, node_mask, edge_mask)`` of a hand-built
    graph, one per case."""
    rng = np.random.default_rng(7)
    if case == "no_live_edges":
        N = 12
        src, dst = rng.integers(0, N, 20), rng.integers(0, N, 20)
        nodes = np.ones(N, bool)
        nodes[[3, 8]] = False
        return N, src, dst, nodes, np.zeros(20, bool)
    if case == "isolated_and_one_edge":
        N = 9
        src, dst = np.array([2, 5, 0]), np.array([6, 7, 1])
        return N, src, dst, np.ones(N, bool), np.array([True, False, False])
    if case == "self_loops":
        N = 8
        src, dst = np.array([1, 3, 1, 6, 6]), np.array([1, 3, 4, 6, 2])
        return N, src, dst, np.ones(N, bool), np.ones(5, bool)
    if case == "dead_bridge":
        # two paths, 0..9 and 10..19, whose only joining edge is dead
        s0, d0 = _path(10)
        src = np.concatenate([s0, s0 + 10, [9]])
        dst = np.concatenate([d0, d0 + 10, [10]])
        edges = np.ones(src.size, bool)
        edges[-1] = False
        return 20, src, dst, np.ones(20, bool), edges
    if case == "long_path":
        # label 0 floods one hop a sweep: 60 sweeps
        N = 60
        src, dst = _path(N)
        return N, src, dst, np.ones(N, bool), np.ones(N - 1, bool)
    if case == "star":
        N = 40
        src = np.full(N - 1, N - 1)
        dst = np.arange(N - 1)
        return N, src, dst, np.ones(N, bool), np.ones(N - 1, bool)
    if case == "many_blocks":
        N, E = 1500, 2400      # 12 blocks of 128 nodes
        src, dst = rng.integers(0, N, E), rng.integers(0, N, E)
        nodes = rng.random(N) < 0.9
        edges = (rng.random(E) < 0.5) & nodes[src] & nodes[dst]
        return N, src, dst, nodes, edges
    if case == "length_boundary":
        N, E = 300, 700
        src, dst = rng.integers(0, N, E), rng.integers(0, N, E)
        edges = np.zeros(E, bool)
        edges[rng.choice(E, 512, replace=False)] = True
        return N, src, dst, np.ones(N, bool), edges
    raise ValueError(case)


@pytest.mark.parametrize("case", ["no_live_edges", "isolated_and_one_edge",
                                  "self_loops", "dead_bridge", "long_path",
                                  "star", "many_blocks", "length_boundary"])
def test_components_fixpoint_exact(case):
    """``ComponentsOp``'s cold solve equals the union-find labels exactly:
    the smallest live id of each component, int32 max for dead nodes."""
    from types import SimpleNamespace

    import analytics_reference as ref
    from repro.core.temporal import ComponentsOp
    from repro.graph.algorithms import fixpoint_lengths

    N, src, dst, nodes, edges = _cc_graph(case)
    src, dst = src.astype(np.int32), dst.astype(np.int32)
    ctx = SimpleNamespace(edge_src=src, edge_dst=dst,
                          universe=SimpleNamespace(num_nodes=N))
    op = ComponentsOp()
    got = op.init(ctx, SimpleNamespace(node_mask=nodes, edge_mask=edges), 0)
    want = ref.components(src, dst, nodes, edges)
    assert got.dtype == np.int32 and np.array_equal(got, want)
    assert op.iters < op.max_iters
    live = int(edges.sum())
    assert op.edges == min(n for n in fixpoint_lengths(src.size)
                           if n >= live)
    if case == "long_path":
        assert op.iters > 20
    if case == "length_boundary":
        assert op.edges == live == fixpoint_lengths(src.size)[0]
    if case == "dead_bridge":
        assert set(got.tolist()) == {0, 10}
