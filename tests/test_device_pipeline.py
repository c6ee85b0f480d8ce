"""Double-buffered device pipeline + fused retrieval + snapshot loader.

Covers the streaming staging layer (`runtime/staging.py`), the chunked
`execute_ir_jax` / `evolve_intervals_jax` paths (must stay bit-identical
to the monolithic call — chunked chain application is a left fold), the
fused singlepoint analytics entry, and `SnapshotBatchLoader`.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import GraphManager, SnapshotBatchLoader, replay
from repro.core.query import NO_ATTRS
from repro.data.generators import random_history
from repro.runtime.jax_exec import (evolve_intervals_jax,
                                    execute_ir_jax,
                                    execute_singlepoint_fused)
from repro.runtime.staging import DeviceStager, stream_chunk_k


# ---------------------------------------------------------------------------
# DeviceStager
# ---------------------------------------------------------------------------


def _logged_stager(depth: int, log: list) -> DeviceStager:
    """A stager whose puts record ``("put", chunk)`` in ``log``; each test
    chunk holds one array filled with its index."""
    def put(x):
        log.append(("put", int(x[0])))
        return x
    return DeviceStager(depth=depth, put_fn=put)


def test_stager_overlap_order():
    """With depth=2, chunk i+1 is built and put before chunk i's apply is
    issued — the double-buffering contract, visible in the call log."""
    log = []
    st = _logged_stager(2, log)
    chunks = [(np.full(4, i),) for i in range(5)]
    seen = []

    def build(i):
        log.append(("build", i))
        return chunks[i]

    def apply(carry, dev):
        seen.append(int(dev[0][0]))
        log.append(("apply", int(dev[0][0])))
        return carry + dev[0].sum()

    out = st.stream(5, build, apply, 0)
    assert out == sum(np.full(4, i).sum() for i in range(5))
    assert seen == [0, 1, 2, 3, 4]
    puts = [i for kind, i in log if kind == "put"]
    applies = [i for kind, i in log if kind == "apply"]
    assert puts == [0, 1, 2, 3, 4] and applies == [0, 1, 2, 3, 4]
    # put(1) precedes apply(0): one chunk always staged ahead
    assert log.index(("put", 1)) < log.index(("apply", 0))
    assert log.index(("put", 2)) < log.index(("apply", 1))
    # every chunk is built before it is put
    for i in range(5):
        assert log.index(("build", i)) < log.index(("put", i))


def test_stager_depth_bound():
    """Never more than `depth` puts ahead of the apply cursor — resident
    staging memory is bounded."""
    log = []
    st = _logged_stager(3, log)
    ahead = []

    def apply(carry, dev):
        puts = sum(1 for k, _ in log if k == "put")
        applies = sum(1 for k, _ in log if k == "apply")
        ahead.append(puts - applies)
        log.append(("apply", int(dev[0][0])))
        return carry

    st.stream(8, lambda i: (np.full(1, i),), apply, None)
    assert max(ahead) <= 3


def test_stager_empty_and_validation():
    st = DeviceStager(put_fn=lambda x: x)
    assert st.stream(0, lambda i: (), lambda c, d: c, "carry") == "carry"
    with pytest.raises(ValueError):
        DeviceStager(depth=0)


def test_stager_with_prefetcher_builds_on_worker():
    from repro.runtime.executor import Prefetcher
    from repro.storage.kv import MemKV
    import threading
    pf = Prefetcher(MemKV(), workers=2)
    main = threading.get_ident()
    build_threads = []

    def build(i):
        build_threads.append(threading.get_ident())
        return (np.full(2, i),)

    st = DeviceStager(depth=2, put_fn=lambda x: x, prefetcher=pf)
    out = st.stream(4, build, lambda c, d: c + int(d[0][0]), 0)
    assert out == 0 + 1 + 2 + 3
    assert all(t != main for t in build_threads)   # built off-thread
    pf.close(wait=True)


def test_stream_chunk_env(monkeypatch):
    monkeypatch.setenv("REPRO_STREAM_CHUNK", "3")
    assert stream_chunk_k() == 3
    monkeypatch.setenv("REPRO_STREAM_CHUNK", "junk")
    assert stream_chunk_k() == 8
    monkeypatch.delenv("REPRO_STREAM_CHUNK")
    assert stream_chunk_k() == 8


# ---------------------------------------------------------------------------
# streamed execution == monolithic execution (bit-identical)
# ---------------------------------------------------------------------------


def _build(seed=3, n_events=150):
    uni, ev = random_history(n_events, seed, max_time_step=2)
    gm = GraphManager(uni, ev, L=8, k=2, cache_bytes=0, prefetch_workers=0)
    return uni, ev, gm


def test_streamed_ir_bit_identical(monkeypatch):
    uni, ev, gm = _build()
    tmax = int(ev.time[-1])
    times = sorted({0, tmax // 3, tmax // 2, tmax})
    ir = gm.dg.plan_multipoint(times, NO_ATTRS, True)
    mono = execute_ir_jax(gm.dg, ir, pool=gm.pool)
    monkeypatch.setenv("REPRO_STREAM_CHUNK", "1")   # force max chunking
    puts = []
    stager = DeviceStager(put_fn=lambda h: puts.append(h.shape) or
                          jnp.asarray(h))
    streamed = execute_ir_jax(gm.dg, ir, pool=gm.pool, stager=stager)
    assert puts                                        # streaming engaged
    for t in times:
        assert np.array_equal(mono[t][0], streamed[t][0]), t
        assert np.array_equal(mono[t][1], streamed[t][1]), t
        truth = replay(uni, ev, t)
        assert np.array_equal(streamed[t][0], truth.node_mask), t
        assert np.array_equal(streamed[t][1], truth.edge_mask), t
    gm.close()


def test_streamed_evolve_bit_identical(monkeypatch):
    uni, ev, gm = _build(seed=4)
    tmax = int(ev.time[-1])
    iv = list(range(0, tmax + 1, max(1, tmax // 9)))
    mono = evolve_intervals_jax(gm.dg, [iv], pool=gm.pool)
    monkeypatch.setenv("REPRO_STREAM_CHUNK", "2")
    stager = DeviceStager()
    streamed = evolve_intervals_jax(gm.dg, [iv], pool=gm.pool,
                                    stager=stager)
    for t in iv:
        assert np.array_equal(mono[0][t][0], streamed[0][t][0]), t
        assert np.array_equal(mono[0][t][1], streamed[0][t][1]), t
        truth = replay(uni, ev, t)
        assert np.array_equal(streamed[0][t][0], truth.node_mask), t
    gm.close()


# ---------------------------------------------------------------------------
# fused singlepoint retrieval + analytics
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", [0, 7])
def test_fused_singlepoint_vs_replay(seed):
    uni, ev, gm = _build(seed=seed, n_events=100)
    rng = np.random.default_rng(seed)
    tmax = int(ev.time[-1])
    for t in (0, tmax // 2, tmax):
        w = rng.random(uni.num_nodes, dtype=np.float32)
        nm, em, an = execute_singlepoint_fused(gm.dg, t, node_weights=w,
                                               pool=gm.pool)
        truth = replay(uni, ev, t)
        assert np.array_equal(nm, truth.node_mask), t
        assert np.array_equal(em, truth.edge_mask), t
        assert an.num_nodes() == int(truth.node_mask.sum())
        assert an.num_edges() == int(truth.edge_mask.sum())
        # weighted push mass == Σ weights over live nodes, exactly (the
        # per-word partials fix the reduction grouping)
        ref = np.zeros(uni.num_nodes, np.float32)
        ref[truth.node_mask] = w[truth.node_mask]
        assert np.float32(an.node.weighted_total()) == np.float32(
            ref.reshape(-1, 1).sum(dtype=np.float32)) or np.isclose(
            an.node.weighted_total(), ref.sum(dtype=np.float32), rtol=1e-6)
        # degrees from the fused live feed == host scatter
        deg = an.degrees()
        rd = np.zeros(uni.num_nodes, np.float32)
        E = uni.num_edges
        for e in np.nonzero(truth.edge_mask)[0]:
            rd[uni.edge_src[e]] += 1
            rd[uni.edge_dst[e]] += 1
        assert np.array_equal(deg, rd), t
    gm.close()


# ---------------------------------------------------------------------------
# SnapshotBatchLoader
# ---------------------------------------------------------------------------


def _loader_history(kind):
    """The cell's kind of history (churn: deletions and transient edges)
    or a growing co-authorship one, small."""
    from repro.core.events import EV_DEL_EDGE
    from repro.data.generators import churn_network, growing_network
    if kind == "churn":
        uni, ev = churn_network(n_initial_edges=90, n_events=300, seed=11,
                                p_transient=0.05)
        assert (ev.etype == EV_DEL_EDGE).any()
        assert uni.edge_transient[:uni.num_edges].any()
    else:
        uni, ev = growing_network(n_events=300, seed=11)
    gm = GraphManager(uni, ev, L=16, k=2, cache_bytes=0, prefetch_workers=0)
    return uni, ev, gm


def _loader_grid(grid, tmax):
    """``(times, label_horizon)``: interior sorted times (7, so batches of
    3 and 4 leave a partial last one); the history's ends, 0 and
    ``tmax``, with a horizon that reaches past the end; or interior times
    each given more than once, out of order."""
    if grid == "interior":
        return sorted({int(t) for t in np.linspace(1, tmax - 1, 7)}), 4
    if grid == "ends":
        return [int(t) for t in np.linspace(0, tmax, 12)], tmax // 2
    inner = sorted({int(t) for t in np.linspace(1, tmax - 1, 6)})
    return inner[::-1] + inner + inner[:3], 4


def _degrees(uni, edge_mask):
    d = np.zeros(uni.num_nodes, np.float32)
    eid = np.nonzero(edge_mask)[0]
    np.add.at(d, uni.edge_src[eid], 1)
    np.add.at(d, uni.edge_dst[eid], 1)
    return d


@pytest.mark.parametrize("grid", ["interior", "ends", "repeated"])
@pytest.mark.parametrize("batch_size", [1, 3, 4])
@pytest.mark.parametrize("history", ["churn", "growing"])
def test_snapshot_batch_loader_oracle(history, batch_size, grid):
    uni, ev, gm = _loader_history(history)
    tmax = int(ev.time[-1])
    times, hz = _loader_grid(grid, tmax)
    loader = SnapshotBatchLoader(gm, times, batch_size=batch_size,
                                 label_horizon=hz, d_in=8)
    N, E = uni.num_nodes, uni.num_edges
    src, dst = uni.edge_src[:E], uni.edge_dst[:E]
    distinct = sorted(set(times))
    seen = []
    n_batches = 0
    for batch in loader:
        T = len(batch["times"])
        seen += batch["times"]
        assert batch["times"] == distinct[n_batches * batch_size:
                                          (n_batches + 1) * batch_size]
        assert batch["x"].shape == (T, N, 8)
        assert np.array_equal(np.asarray(batch["edge_index"]),
                              [np.concatenate([src, dst]),
                               np.concatenate([dst, src])])
        assert batch["edge_mask"].shape == (T, 2 * E)
        assert batch["labels"].shape == (T, N)
        for j, t in enumerate(batch["times"]):
            truth = replay(uni, ev, t)
            assert np.array_equal(
                np.asarray(batch["label_mask"][j]) > 0, truth.node_mask)
            live = truth.edge_mask[:E].astype(np.float32)
            assert np.array_equal(np.asarray(batch["edge_mask"][j]),
                                  np.concatenate([live, live]))
            rd = _degrees(uni, truth.edge_mask)
            assert np.array_equal(np.asarray(batch["x"][j, :, -1]), rd)
            assert int(batch["num_edges"][j]) == int(truth.edge_mask.sum())
            fd = _degrees(uni, replay(uni, ev, t + hz).edge_mask)
            assert np.array_equal(np.asarray(batch["labels"][j]),
                                  (fd > rd).astype(np.int32))
        n_batches += 1
    assert n_batches == len(loader) == len(distinct) // batch_size
    if grid == "ends":
        assert {0, tmax} <= set(seen) and hz > 0
    gm.close()


def test_snapshot_batch_loader_no_horizon():
    uni, ev, gm = _build(seed=12, n_events=80)
    tmax = int(ev.time[-1])
    loader = SnapshotBatchLoader(gm, [0, tmax // 2, tmax], batch_size=3)
    (batch,) = list(loader)
    assert "labels" not in batch
    assert batch["x"].shape[0] == 3
    with pytest.raises(ValueError):
        SnapshotBatchLoader(gm, [0], batch_size=0)
    gm.close()


# ---------------------------------------------------------------------------
# prefetcher worker plumbing
# ---------------------------------------------------------------------------


def test_prefetcher_submit_fn_and_decode_nice():
    from repro.runtime.executor import Prefetcher
    from repro.storage import codec
    from repro.storage.kv import MemKV
    pf = Prefetcher(MemKV(), workers=1)
    assert pf.submit_fn(lambda a, b: a + b, 2, 3).result() == 5

    # the worker installs a decode-nice hook; verify the hook fires in
    # _decode_v2 by installing a counting hook on this thread
    calls = []
    codec.set_decode_nice(lambda: calls.append(1))
    try:
        blob = codec.encode_blob(
            {"a": np.arange(5), "b": np.ones(3, np.float32)}, codec="v2")
        codec.set_decode_cache_bytes(0)     # bypass the decode cache
        out = codec.decode_blob(blob)
        assert np.array_equal(out["a"], np.arange(5))
        assert len(calls) == 2              # once per array
    finally:
        codec.set_decode_nice(None)
        codec.set_decode_cache_bytes(64 * 2 ** 20)
    pf.close(wait=True)
