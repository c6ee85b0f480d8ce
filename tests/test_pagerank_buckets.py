"""The bucketed PageRank path (``graph/algorithms.py``): a universe's
directed edge entries sorted by target once (``target_table``), a solve's
live entries laid into rows that each stay inside one 128-node target
block (``row_layout``), and the fixpoint kernel that reduces them.

Two universes above the dense threshold: the churn rehearsal universe's
edges folded onto 1,100 nodes (as ``test_served_analytics`` folds them),
and a skewed one in which one target block holds most live entries.
Live counts from none to every slot.  The layout must hold every live
directed entry once, each row inside its block, padding with no mass,
within ``_row_count`` rows; the ranks must match the dense kernel and
the float64 reference, and the Pallas reducer (interpret mode) the XLA
one.
"""
from __future__ import annotations

from collections import Counter

import numpy as np
import pytest

import analytics_reference as ref
from repro.core import bitmaps as bm
from repro.data.generators import churn_network
from repro.graph import algorithms as alg

N = 1100                      # above DENSE_PAGERANK_MAX_NODES
NB = -(-N // alg.TARGET_BLOCK)
HOT = 3                       # the skewed universe's crowded block


def _folded():
    uni, _ = churn_network(n_initial_edges=1000, n_events=10000, seed=5)
    return uni.edge_src % N, uni.edge_dst % N


def _skewed():
    rng = np.random.default_rng(11)
    E = 3000
    src = rng.integers(0, N, E).astype(np.int32)
    dst = rng.integers(0, N, E).astype(np.int32)
    hot = rng.random(E) < 0.85     # both ends in block HOT
    lo = HOT * alg.TARGET_BLOCK
    src[hot] = rng.integers(lo, lo + alg.TARGET_BLOCK, hot.sum())
    dst[hot] = rng.integers(lo, lo + alg.TARGET_BLOCK, hot.sum())
    return src, dst


UNIVERSES = {"folded": _folded, "skewed": _skewed}


@pytest.fixture(scope="module", params=sorted(UNIVERSES))
def universe(request):
    src, dst = UNIVERSES[request.param]()
    return request.param, src, dst, alg.target_table(src, dst, N)


def _mask(E: int, live: str) -> np.ndarray:
    """``live`` edge slots of ``E``, drawn from a fixed seed."""
    n = {"none": 0, "one": 1, "partial_row": 45, "half": E // 2,
         "all": E}[live]
    m = np.zeros(E, bool)
    m[np.random.default_rng(3).permutation(E)[:n]] = True
    return m


LIVE = ["none", "one", "partial_row", "half", "all"]


def test_skewed_universe_crowds_one_block(universe):
    name, src, dst, table = universe
    per_block = np.bincount(table.target // alg.TARGET_BLOCK, minlength=NB)
    if name == "skewed":
        assert per_block[HOT] > 0.5 * table.target.size
    assert np.array_equal(np.diff(table.block_start), per_block)
    assert np.all(np.diff(table.target) >= 0)


@pytest.mark.parametrize("live", LIVE)
def test_layout_holds_each_live_entry_once_in_its_block(universe, live):
    _, src, dst, table = universe
    E = src.size
    emask = _mask(E, live)
    Ec = alg._edge_length(int(emask.sum()), E)
    rows = alg._row_count(Ec, N)
    lay = alg.row_layout(table, emask, N, rows)
    C = alg.ROW_LANES
    assert lay.other.shape == lay.local.shape == (rows * C,)
    assert lay.row_block.shape == (rows,)
    # rows used: within R(Ec) = ⌈2·Ec / C⌉ + NB, one block each
    used = lay.row_block < NB
    assert used.sum() <= -(-2 * Ec // C) + NB
    assert np.all(np.diff(lay.row_block) >= 0)
    assert np.array_equal(used, np.arange(rows) < used.sum())
    # every live directed entry once, at its target's block and offset
    local = lay.local.reshape(rows, C)
    other = lay.other.reshape(rows, C)
    real = local >= 0
    target = (lay.row_block[:, None] * alg.TARGET_BLOCK + local)[real]
    live_e = np.flatnonzero(emask)
    want = Counter(zip(np.r_[dst[live_e], src[live_e]].tolist(),
                       np.r_[src[live_e], dst[live_e]].tolist()))
    assert Counter(zip(target.tolist(), other[real].tolist())) == want
    assert np.all(local < alg.TARGET_BLOCK)
    # padding: off-block, gathering the zero slot; unused rows all padding
    assert np.all(other[~real] == N)
    assert not real[~used].any()
    assert np.array_equal(lay.deg, np.bincount(
        np.r_[src[live_e], dst[live_e]], minlength=N).astype(np.float32))


def _solve(src, dst, table, emask, **kw):
    pr0 = np.full(N, 1 / N, np.float32)
    return alg.pagerank_fixpoint(src, dst, bm.np_pack(emask),
                                 bm.np_pack(np.ones(N, bool)), pr0,
                                 num_nodes=N, table=table, **kw)


@pytest.mark.parametrize("live", LIVE)
def test_ranks_match_dense_kernel_and_float64_reference(universe, live):
    _, src, dst, table = universe
    emask = _mask(src.size, live)
    got = _solve(src, dst, table, emask)
    dense = _solve(src, dst, table, emask, force_impl="dense")
    truth = ref.pagerank(src, dst, np.ones(N, bool), emask)
    assert got.edges == alg._edge_length(int(emask.sum()), src.size)
    assert np.abs(got.value - dense.value).sum() <= 1e-5
    assert np.abs(got.value.astype(np.float64) - truth).sum() <= 1e-5
    assert abs(got.iters - dense.iters) <= 1


@pytest.mark.parametrize("live", ["partial_row", "half", "all"])
def test_pallas_and_xla_reducers_agree(universe, live):
    _, src, dst, table = universe
    emask = _mask(src.size, live)
    Ec = alg._edge_length(int(emask.sum()), src.size)
    lay = alg.row_layout(table, emask, N, alg._row_count(Ec, N))
    args = (lay, bm.np_pack(np.ones(N, bool)), np.full(N, 1 / N, np.float32),
            0.85, 1e-6, N, alg.PAGERANK_MAX_ITERS)
    xla, it_x = alg._pagerank_segment(*args, impl="xla")
    pallas, it_p = alg._pagerank_segment(*args, impl="pallas",
                                         interpret=True)
    assert np.abs(xla - pallas).sum() <= 1e-6
    assert abs(it_x - it_p) <= 1


def test_layout_refuses_too_few_rows(universe):
    _, src, dst, table = universe
    emask = _mask(src.size, "all")
    with pytest.raises(ValueError, match="rows"):
        alg.row_layout(table, emask, N, 1)


def test_evolve_builds_the_table_once_per_universe(monkeypatch):
    """``PageRankOp`` keeps the table in the universe's memo: one build
    over an evolve document's points, and the points' ranks match the
    float64 reference."""
    from repro.core import GraphManager
    uni, ev = churn_network(n_initial_edges=3300, n_events=3000, seed=3)
    assert uni.num_nodes > alg.DENSE_PAGERANK_MAX_NODES
    gm = GraphManager(uni, ev, L=500, k=2)
    builds = []
    real = alg.target_table
    monkeypatch.setattr(alg, "target_table",
                        lambda *a: builds.append(1) or real(*a))
    tmax = int(ev.time[-1])
    times = [tmax // 4, tmax // 2, 3 * tmax // 4]
    res = dict(gm.evolve(times, "pagerank", tol=1e-7))
    assert len(builds) == 1
    for t in times:
        nm, em = ref.masks(uni, ev, t)
        truth = ref.pagerank(uni.edge_src, uni.edge_dst, nm, em)
        assert np.abs(np.asarray(res[t], np.float64) - truth).sum() <= 1e-5
    gm.close()
