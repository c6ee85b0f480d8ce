"""`segment_sum`'s bucket tables, built once per universe
(`runtime/jax_exec.edge_buckets`) and kept on the device.

The Pallas path (interpret mode on the CPU) of `SnapshotBatchLoader` and
`SnapshotAnalytics.degrees` takes the cached tables; they must give the
degrees the uncached wrapper and a host bincount give, be built once per
side across loaders, be rebuilt after an append, and not keep a dropped
universe alive.
"""
import gc
import weakref

import numpy as np
import pytest

import jax

from repro.core import GraphManager, SnapshotBatchLoader, replay
from repro.data.generators import churn_network
from repro.kernels import segment_sum
from repro.runtime import jax_exec


def _history(seed=5):
    return churn_network(n_initial_edges=150, n_events=1200, seed=seed)


def _bincount(uni, edge_mask):
    E, N = uni.num_edges, uni.num_nodes
    live = np.asarray(edge_mask[:E], bool)
    return (np.bincount(uni.edge_src[:E][live], minlength=N)
            + np.bincount(uni.edge_dst[:E][live], minlength=N)
            ).astype(np.float32)


def _uncached(uni, edge_mask):
    """The wrapper bucketing the host ids itself, as every call did."""
    E, N = uni.num_edges, uni.num_nodes
    live = jax.numpy.asarray(edge_mask[:E], np.float32)[:, None]
    return np.asarray(
        segment_sum(live, uni.edge_src[:E], N, impl="pallas")
        + segment_sum(live, uni.edge_dst[:E], N, impl="pallas")).reshape(-1)


@pytest.fixture
def builds(monkeypatch):
    """The bucketing calls made through the cache, one per side built."""
    calls, real = [], jax_exec.bucket_edges

    def counted(ids, num_segments, block_n):
        calls.append((len(ids), num_segments, block_n))
        return real(ids, num_segments, block_n)

    monkeypatch.setattr(jax_exec, "bucket_edges", counted)
    return calls


def _times(ev, n=4):
    tmax = int(ev.time[-1])
    return list(range(tmax // 8, tmax // 2, tmax // 10))[:n], tmax // 20


def test_loader_degrees_match_uncached_and_bincount(builds):
    uni, ev = _history()
    gm = GraphManager(uni, ev, L=64, k=2, cache_bytes=0)
    times, hz = _times(ev)
    for _ in range(2):                        # two loaders, one universe
        loader = SnapshotBatchLoader(gm, times, batch_size=4,
                                     label_horizon=hz, d_in=8,
                                     impl="pallas")
        batch = next(iter(loader))
        for ts in (times, [t + hz for t in times]):   # window, horizon
            masks = [replay(uni, ev, t).edge_mask for t in ts]
            deg, num_edges = loader._degrees(masks)
            for j, em in enumerate(masks):
                want = _bincount(uni, em)
                assert np.array_equal(deg[j], want), ts[j]
                assert np.array_equal(deg[j], _uncached(uni, em)), ts[j]
                assert num_edges[j] == em.sum()
        x_deg = np.asarray(batch["x"])[:, :, -1]
        for j, t in enumerate(times):
            assert np.array_equal(
                x_deg[j], _bincount(uni, replay(uni, ev, t).edge_mask))
    gm.close()
    E, N = uni.num_edges, uni.num_nodes
    assert builds == [(E, N, 128), (E, N, 128)]


def test_append_rebuilds_and_stays_exact(builds):
    uni, ev = _history(seed=6)
    gm = GraphManager(uni, ev, L=64, k=2, cache_bytes=0)
    times, hz = _times(ev)
    loader = SnapshotBatchLoader(gm, times, batch_size=4, label_horizon=hz,
                                 d_in=8, impl="pallas")
    next(iter(loader))
    assert len(builds) == 2
    first = jax_exec.edge_buckets(uni)
    assert len(builds) == 2                   # a hit
    # a new node slot and an edge slot joining it to node 0
    n = uni.node_slot(("appended", 0), create=True)
    uni.new_edge_slot(("appended", 1), n, 0, directed=False)
    masks = [replay(uni, ev, t).edge_mask for t in times]
    for em in masks:
        assert em.shape == (uni.num_edges,) and not em[-1]
        em[-1] = True                         # the new edge is live
    deg, _ = loader._degrees(masks)
    assert len(builds) == 4
    assert builds[2:] == [(uni.num_edges, uni.num_nodes, 128)] * 2
    assert jax_exec.edge_buckets(uni) is not first
    for j, em in enumerate(masks):
        assert deg[j].shape == (uni.num_nodes,)
        assert np.array_equal(deg[j], _bincount(uni, em)), times[j]
        assert deg[j][n] == 1
    gm.close()


def test_cache_does_not_keep_a_universe_alive(builds):
    uni, _ = _history(seed=7)
    tables = jax_exec.edge_buckets(uni)
    assert jax_exec.edge_buckets(uni) is tables and len(builds) == 2
    assert all(isinstance(a, jax.Array)
               for side in tables for a in side[:2])
    ref = weakref.ref(uni)
    del uni
    gc.collect()
    assert ref() is None


def test_snapshot_analytics_degrees_through_the_cache(builds):
    uni, ev = _history(seed=8)
    gm = GraphManager(uni, ev, L=64, k=2, cache_bytes=0)
    times, _ = _times(ev, n=2)
    for t in times:
        *_, an = jax_exec.execute_singlepoint_fused(gm.dg, t, pool=gm.pool,
                                                    impl="pallas")
        em = replay(uni, ev, t).edge_mask
        deg = an.degrees(impl="pallas")
        assert np.array_equal(deg, _bincount(uni, em)), t
        assert np.array_equal(deg, an.degrees(impl="xla")), t
    gm.close()
    assert len(builds) == 2
