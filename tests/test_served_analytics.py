"""Served evolve analytics against the plain float64 reference
(``tests/analytics_reference.py``), and the fixpoint solvers' shape policy.

Whole documents go through the server's session path (``SessionCore`` and
the scheduler, as the stdin fallback drives them) at the rehearsal sizes
of the benchmark's ``churn-1m-served`` configuration, over seeds, and come
back as wire lines:

* ``multipoint`` and ``components`` must equal the reference exactly
  (counts and CRCs, as the envelope carries them);
* each ``pagerank`` point must lie within the benchmark cell's L1 limit
  of the float64 fixpoint (the limit's reason is in PERF.md §2 and in
  ``bench/traffic/serve-evolve.json``).
"""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np
import pytest

import analytics_reference as ref
from repro.api.scheduler import BatchingScheduler
from repro.core import GraphManager, bitmaps as bm
from repro.data.generators import churn_network
from repro.graph import algorithms as alg
from repro.launch.server import SessionCore, run_session_lines

ROOT = Path(__file__).resolve().parents[1]
TRAFFIC = json.loads((ROOT / "bench/traffic/serve-evolve.json").read_text())
CONFIG = ROOT / "bench/configs/churn-1m-served.json"
REHEARSE = json.loads(CONFIG.read_text())["rehearse"]


def _history(seed: int):
    h = REHEARSE["history"]
    return churn_network(n_initial_edges=h["n_initial_edges"],
                         n_events=h["n_events"], seed=seed)


def _documents(kind: str, tmax: int, rng) -> list[dict]:
    """Three documents of ``kind`` over windows of 5 % of the history."""
    points = 4 if kind == "multipoint" else 8
    docs = []
    for i in range(3):
        width = int(tmax * TRAFFIC["span"])
        start = int(rng.integers(0, tmax - width))
        times = [int(t) for t in np.linspace(start, start + width, points)]
        doc = {"kind": "multipoint", "times": times, "id": i}
        if kind != "multipoint":
            doc = {"kind": "evolve", "op": kind, "times": times, "id": i,
                   "reply": "full" if kind == "pagerank" else "summary"}
        docs.append(doc)
    return docs


def _serve(gm, docs: list[dict]) -> list[dict]:
    sched = BatchingScheduler(gm.query)
    try:
        core = SessionCore(gm, sched)
        return [json.loads(line) for line in
                run_session_lines(core, [json.dumps(d) for d in docs])]
    finally:
        sched.close()


@pytest.mark.parametrize("seed", [3, 2**31 + 7])
@pytest.mark.parametrize("kind", ["pagerank", "components", "multipoint"])
def test_served_documents_match_the_reference(kind, seed, monkeypatch):
    # the segment kernel, which the cell's 33,333-node universe runs
    monkeypatch.setattr(alg, "DENSE_PAGERANK_MAX_NODES", 0)
    uni, ev = _history(seed)
    idx = REHEARSE["index"]
    gm = GraphManager(uni, ev, L=idx["L"], k=2, diff_fn="intersection")
    docs = _documents(kind, int(ev.time[-1]), np.random.default_rng(seed))
    try:
        envs = _serve(gm, docs)
    finally:
        gm.close()
    limit = TRAFFIC["limits"]["pagerank_l1_max"]
    for doc, env in zip(docs, envs):
        assert env["ok"] and env["id"] == doc["id"], env
        res = env["result"]
        if kind == "multipoint":
            got = [[p["t"], p["nodes"], p["edges"], p["node_crc"],
                    p["edge_crc"]] for p in res["points"]]
        else:
            assert res["times"] == doc["times"]
            got = res["values"]
        for t, g in zip(doc["times"], got):
            nm, em = ref.masks(uni, ev, t)
            if kind == "multipoint":
                assert g == [t, int(nm.sum()), int(em.sum()),
                             ref.crc(np.packbits(nm)),
                             ref.crc(np.packbits(em))]
            elif kind == "components":
                want = ref.components(uni.edge_src, uni.edge_dst, nm, em)
                assert g == {"size": want.size, "dtype": "int32",
                             "crc": ref.crc(want)}
            else:
                want = ref.pagerank(uni.edge_src, uni.edge_dst, nm, em)
                assert np.abs(np.asarray(g) - want).sum() <= limit


@pytest.mark.parametrize("num_edges", [0, 1, 511, 512, 513, 6600, 606_000,
                                       1_414_000])
def test_fixpoint_lengths_are_few_and_cover_every_live_count(num_edges):
    lengths = alg.fixpoint_lengths(num_edges)
    assert lengths[0] == 512 and lengths[-1] >= num_edges
    assert list(lengths) == sorted(set(lengths))
    assert all(n % 128 == 0 for n in lengths)
    # four to a doubling: about 4 log2(E / 512) lengths in all
    assert len(lengths) <= 4 * max(np.log2(max(num_edges, 1) / 512), 0) + 5
    # each length wastes under a quarter of the one below it
    assert all(b <= 1.25 * a for a, b in zip(lengths, lengths[1:]))
    for n in {0, 1, num_edges // 3, num_edges}:
        got = alg._edge_length(n, num_edges)
        assert got in lengths and got >= n
        assert got == 512 or lengths[lengths.index(got) - 1] < n


@pytest.fixture(scope="module")
def snapshot():
    """One churn snapshot, packed as the evolve operators pass it."""
    uni, ev = _history(5)
    nm, em = ref.masks(uni, ev, int(ev.time[-1]) // 2)
    return uni, bm.np_pack(nm), bm.np_pack(em), nm


@pytest.mark.parametrize("op", ["pagerank", "components"])
def test_padded_and_unpadded_solves_agree_bit_for_bit(snapshot, op):
    uni, nplane, eplane, nm = snapshot
    N = uni.num_nodes
    emask = bm.np_unpack(eplane, uni.num_edges)
    live = int(emask.sum())
    es, ed, lv = alg._compact_edges(uni.edge_src, uni.edge_dst, emask)
    assert es.size in alg.fixpoint_lengths(uni.num_edges) and es.size > live
    pr0 = nm.astype(np.float32) / nm.sum()
    out = []
    for n in (live, es.size):
        if op == "pagerank":
            out.append(alg._pagerank_segment(
                es[:n], ed[:n], lv[:n], nplane, pr0, 0.85, 1e-6, N,
                alg.PAGERANK_MAX_ITERS))
        else:
            out.append(alg._components_segment(
                es[:n], ed[:n], lv[:n], nplane, np.arange(N, dtype=np.int32),
                N, alg.COMPONENTS_MAX_ITERS))
    (a, ia), (b, ib) = out
    assert ia == ib and a.tobytes() == b.tobytes()


def test_compile_fixpoints_leaves_no_length_to_compile(snapshot):
    """After start-up's compile, solves at any live count hit the cache."""
    uni, nplane, _, nm = snapshot
    N, E = 1100, uni.num_edges        # above the dense PageRank threshold
    src, dst = uni.edge_src % N, uni.edge_dst % N
    nplane = bm.np_pack(np.ones(N, bool))
    assert alg.compile_fixpoints(N, E) == 2 * len(alg.fixpoint_lengths(E))
    kernels = (alg._pagerank_fixpoint_kernel, alg._cc_fixpoint_kernel)
    before = [k._cache_size() for k in kernels]
    for live in (0, 700, E // 2, E):
        eplane = bm.np_pack(np.arange(E) < live)
        alg.pagerank_fixpoint(src, dst, eplane, nplane,
                              np.full(N, 1 / N, np.float32), num_nodes=N)
        alg.connected_components_fixpoint(src, dst, eplane, nplane,
                                          np.arange(N, dtype=np.int32),
                                          num_nodes=N)
    assert [k._cache_size() for k in kernels] == before
    # the programs compiled are those the evolve operators run
    from repro.core.temporal import ComponentsOp, PageRankOp
    assert PageRankOp().max_iters == alg.PAGERANK_MAX_ITERS
    assert ComponentsOp().max_iters == alg.COMPONENTS_MAX_ITERS


def test_envelope_stats_carry_the_served_times():
    uni, ev = _history(9)
    gm = GraphManager(uni, ev, L=REHEARSE["index"]["L"], k=2,
                      diff_fn="intersection")
    rng = np.random.default_rng(9)
    tmax = int(ev.time[-1])
    docs = (_documents("pagerank", tmax, rng)[:1]
            + _documents("components", tmax, rng)[:1]
            + _documents("multipoint", tmax, rng)[:1])
    try:
        envs = _serve(gm, docs)
    finally:
        gm.close()
    for doc, env in zip(docs, envs):
        st = env["stats"]
        assert all(st[k] >= 0 for k in ("queue_s", "advance_s", "solve_s",
                                         "reply_s"))
        assert st["advance_s"] + st["solve_s"] <= st["wall_s"]
        if doc["kind"] == "evolve":
            assert st["advance_s"] > 0 and st["solve_s"] > 0
            engine = env["result"]["engine_stats"]
            assert len(engine["solver_iters"]) == len(doc["times"])
            assert len(engine["solver_edges"]) == len(doc["times"])
        else:
            assert st["advance_s"] == st["solve_s"] == 0


def test_a_free_worker_admits_work_above_the_horizon():
    """Admission sheds only once every worker is taken: evolve documents
    whose estimates pass a tiny drain horizon still run while a worker is
    free for each."""
    from repro.api.document import GraphQuery
    uni, ev = _history(11)
    gm = GraphManager(uni, ev, L=REHEARSE["index"]["L"], k=2,
                      diff_fn="intersection")
    tmax = int(ev.time[-1])
    docs = [GraphQuery.from_dict(d) for d in
            _documents("components", tmax, np.random.default_rng(11))[:2]]
    sched = BatchingScheduler(gm.query, workers=2, admit_horizon_ms=1e-3)
    sched.solo_s.value = 10.0           # each estimate: 10 s of work
    try:
        results = [f.result(timeout=60)
                   for f in [sched.submit(d) for d in docs]]
    finally:
        sched.close()
        gm.close()
    assert all(r.ok for r in results), [r.error for r in results]
    assert sched.counters["shed_overload"] == 0


def _any_bits(n: int) -> np.ndarray:
    bits = np.random.default_rng(1).integers(0, 2**32, n, dtype=np.uint64)
    v = bits.astype(np.uint32).view(np.float32)
    return v[np.isfinite(v)]


_VECTORS = {
    "ranks": (np.random.default_rng(0).random(33_333) / 33_333
              ).astype(np.float32),
    "extremes": np.array([0.0, -0.0, 1e-45, 1.1754944e-38, 3.4028235e38,
                          -3.4028235e38, 1.0, 0.1, 9.9999999e-5, 1e-5,
                          123456789.0, -5.5], np.float32),
    "any_bits": _any_bits(100_000),
}


@pytest.mark.parametrize("name", sorted(_VECTORS))
def test_full_float32_vectors_are_written_exactly(name):
    """The wire encoder writes each float32 of a full reply so that it
    reads back as the same float32, in the envelope ``json`` would make."""
    from repro.api.service import dumps
    v = _VECTORS[name]
    env = {"v": 1, "id": "x", "result": {"values": [v, v[::-1].copy()],
                                         "times": [1, 2]}}
    back = json.loads(dumps(env))
    assert list(back) == sorted(back) and back["id"] == "x"
    assert back["result"]["times"] == [1, 2]
    for got, want in zip(back["result"]["values"], (v, v[::-1])):
        got = np.asarray(got, np.float32)
        assert got.tobytes() == np.where(want == 0, 0, want).tobytes()


def test_dumps_writes_lists_when_a_string_holds_its_marker(monkeypatch):
    from repro.api import service
    monkeypatch.setattr(service.secrets, "token_hex", lambda n: "m")
    v = _VECTORS["extremes"]
    back = json.loads(service.dumps({"id": "m", "values": [v]}))
    assert back["id"] == "m"
    assert back["values"] == [v.tolist()]
