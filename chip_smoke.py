#!/usr/bin/env python3
"""Smoke test of the device retrieval path on one TPU, at DBLP scale.

    python chip_smoke.py [--seed N]          # one chip: every phase below
    python chip_smoke.py --chips 4           # four chips: sharded retrieval
    python chip_smoke.py --rehearse [...]    # small histories, CPU, Pallas
                                             # kernels in interpret mode

Phases, in order, each in this one process (an accelerator belongs to one
process at a time):

* device   — the platform must be a TPU and the kernel policy
             ``("pallas", False)``: no fallback to XLA or the interpreter;
* build    — a co-authorship history (``growing_network``, 2M events:
             ~600k node and ~1.4M edge slots, the size of DBLP) and a churn
             history with deletions and transients (1M events), each
             indexed by a ``GraphManager(L=10_000, k=2)``;
* retrieve — batched multipoint retrieval (16 timepoints, once landed in
             the GraphPool) and fused retrieval + analytics (4 timepoints)
             on both histories; masks must equal ``events.replay`` bit for
             bit, counts and degrees must equal numpy over those masks;
* evolve   — incremental connected components and PageRank over a
             32-point interval of the churn history against per-snapshot
             recompute;
* train    — 10 steps of the temporal GCN example fed by
             ``SnapshotBatchLoader`` on the churn history;
* serve    — 8 GraphQuery documents through ``gm.query.run_batch`` (host
             path), every envelope checked against the replay oracle.

With ``--chips 4`` only the growing history is built, with 4 word-cyclic
storage partitions, and retrieved by the sharded path, one partition row
per chip, against the one-chip path and the oracle.

Everything is generated from ``--seed``; nothing is downloaded.  The last
line of the output is one JSON object naming the device; the script exits
non-zero, before that line, if any phase fails or JAX finds no TPU.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent

# (full run, rehearsal) sizes
GROWING_EVENTS = (2_000_000, 20_000)
CHURN_EVENTS = (1_000_000, 10_000)
CHURN_INITIAL_EDGES = (100_000, 1_000)
LEAF_EVENTS = (10_000, 1_000)

WEIGHT_RTOL = 1e-5      # f32 weighted total vs the float64 sum
PAGERANK_L1 = 1e-4      # warm vs cold ranks, each solved to L1 step < 1e-6


class SmokeFailure(Exception):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


class Phases:
    """Runs named phases; each prints its wall time, the time spent
    compiling in it, and what the persistent compile cache did."""

    def __init__(self):
        from jax import monitoring
        self.compile_s = 0.0
        self.cache = {"hits": 0, "misses": 0}
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache["misses"] += 1

    @contextlib.contextmanager
    def phase(self, name: str):
        t0, c0 = time.perf_counter(), self.compile_s
        h0, m0 = self.cache["hits"], self.cache["misses"]
        print(f"[{name}] start", flush=True)
        try:
            yield
        except Exception as e:
            print(f"[{name}] FAILED: {type(e).__name__}: {e}", flush=True)
            raise
        print(f"[{name}] ok  wall={time.perf_counter() - t0:.2f}s  "
              f"compile={self.compile_s - c0:.2f}s  "
              f"cache_hits={self.cache['hits'] - h0}  "
              f"cache_misses={self.cache['misses'] - m0}", flush=True)


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def device_phase(rehearse: bool, chips: int):
    import jax
    from repro.kernels import policy
    devs = jax.devices()
    d0 = devs[0]
    pol = policy.resolve()
    print(f"  devices={devs}")
    print(f"  platform={d0.platform} device_kind={d0.device_kind} "
          f"count={len(devs)} kernel_policy={pol}")
    if not rehearse:
        check(d0.platform == "tpu",
              f"no TPU: JAX found platform {d0.platform!r} "
              f"({d0.device_kind}); --rehearse runs on the CPU")
    want = ("pallas", bool(rehearse))
    check(pol == want, f"kernel policy {pol}, want {want}")
    check(len(devs) >= chips, f"{chips} chips asked for, {len(devs)} found")
    return d0, len(devs)


def build_history(kind: str, size: int, seed: int, **gm_kw):
    from repro.core import GraphManager
    from repro.data.generators import churn_network, growing_network
    t0 = time.perf_counter()
    if kind == "growing":
        uni, ev = growing_network(n_events=GROWING_EVENTS[size], seed=seed,
                                  attrs_on_add=False)
    else:
        uni, ev = churn_network(n_initial_edges=CHURN_INITIAL_EDGES[size],
                                n_events=CHURN_EVENTS[size], seed=seed)
    t1 = time.perf_counter()
    gm = GraphManager(uni, ev, L=LEAF_EVENTS[size], k=2,
                      diff_fn="intersection", **gm_kw)
    t2 = time.perf_counter()
    print(f"  {kind}: events={len(ev)} node_slots={uni.num_nodes} "
          f"edge_slots={uni.num_edges} leaves={len(gm.dg.leaf_nids)} "
          f"partitions={gm.dg.P}  generate={t1 - t0:.2f}s "
          f"index={t2 - t1:.2f}s", flush=True)
    return uni, ev, gm


def pick_times(rng, ev, n: int) -> list[int]:
    """``n`` distinct timepoints spread over the whole history."""
    import numpy as np
    tmax = int(ev.time[-1])
    pool = np.unique(rng.integers(0, tmax + 1, 4 * n))
    return sorted(int(t) for t in rng.permutation(pool)[:n])


class Oracle:
    """``events.replay`` snapshots, each computed once."""

    def __init__(self, uni, ev):
        self.uni, self.ev, self._memo = uni, ev, {}

    def __call__(self, t: int):
        from repro.core import replay
        if t not in self._memo:
            self._memo[t] = replay(self.uni, self.ev, t)
        return self._memo[t]


def degrees_of(uni, edge_mask):
    import numpy as np
    live = np.nonzero(edge_mask)[0]
    N = uni.num_nodes
    return (np.bincount(uni.edge_src[live], minlength=N)
            + np.bincount(uni.edge_dst[live], minlength=N))


def kernel_in_hlo(fn, *shapes) -> bool:
    import jax
    return "tpu_custom_call" in jax.jit(fn).lower(*shapes).compile(
    ).as_text()


def retrieve_phase(name: str, uni, ev, gm, rng):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import bitmaps as bmod
    from repro.kernels import (delta_apply_chain_batched,
                               delta_apply_fused_batched, policy)
    from repro.runtime.jax_exec import (execute_multipoint_jax,
                                        execute_singlepoint_fused)
    oracle = Oracle(uni, ev)
    times = pick_times(rng, ev, 16)
    print(f"  {name}: multipoint times={times}")
    t0 = time.perf_counter()
    masks = execute_multipoint_jax(gm.dg, times, pool=gm.pool,
                                   prefetch=gm.prefetcher)
    print(f"  {name}: execute_multipoint_jax 16 points "
          f"{time.perf_counter() - t0:.2f}s")
    for t in times:
        truth = oracle(t)
        check(np.array_equal(masks[t][0], truth.node_mask),
              f"{name} t={t}: node mask != replay")
        check(np.array_equal(masks[t][1], truth.edge_mask),
              f"{name} t={t}: edge mask != replay")
    t0 = time.perf_counter()
    gids = execute_multipoint_jax(gm.dg, times, pool=gm.pool,
                                  prefetch=gm.prefetcher, land_in_pool=True)
    print(f"  {name}: execute_multipoint_jax land_in_pool "
          f"{time.perf_counter() - t0:.2f}s")
    for t, gid in gids.items():
        truth = oracle(t)
        check(np.array_equal(gm.pool.get_node_mask(gid), truth.node_mask)
              and np.array_equal(gm.pool.get_edge_mask(gid),
                                 truth.edge_mask),
              f"{name} t={t}: pooled snapshot != replay")
        gm.pool.release(gid)
    print(f"  {name}: 16 multipoint masks and 16 pooled snapshots == replay")

    weights = rng.random(uni.num_nodes, dtype=np.float32)
    for t in times[::4]:
        t0 = time.perf_counter()
        nm, em, an = execute_singlepoint_fused(gm.dg, t, node_weights=weights,
                                               pool=gm.pool)
        deg = an.degrees()
        wall = time.perf_counter() - t0
        truth = oracle(t)
        check(np.array_equal(nm, truth.node_mask)
              and np.array_equal(em, truth.edge_mask),
              f"{name} t={t}: fused masks != replay")
        n_live, e_live = int(truth.node_mask.sum()), int(truth.edge_mask.sum())
        check(an.num_nodes() == n_live,
              f"{name} t={t}: num_nodes {an.num_nodes()} != {n_live}")
        check(an.num_edges() == e_live,
              f"{name} t={t}: num_edges {an.num_edges()} != {e_live}")
        want_deg = degrees_of(uni, truth.edge_mask)
        check(np.array_equal(deg, want_deg.astype(deg.dtype)),
              f"{name} t={t}: degrees != numpy bincount")
        wt = float(an.node.weighted_total())
        ref = float(weights[truth.node_mask].astype(np.float64).sum())
        check(abs(wt - ref) <= WEIGHT_RTOL * max(abs(ref), 1.0),
              f"{name} t={t}: weighted_total {wt} vs float64 {ref}")
        print(f"  {name}: fused t={t} nodes={n_live} edges={e_live} "
              f"max_degree={int(want_deg.max())} weighted_total={wt!r} "
              f"(float64 {ref!r}) {wall:.2f}s")

    # the compiled programs of both entry points at this history's width
    W = bmod.num_words(uni.num_edges)
    planes = [jax.ShapeDtypeStruct(s, jnp.uint32)
              for s in ((16, W), (16, 8, W), (16, 8, W))]
    fused = kernel_in_hlo(delta_apply_fused_batched, *planes)
    batched = kernel_in_hlo(delta_apply_chain_batched, *planes)
    print(f"  {name}: tpu_custom_call in compiled HLO: fused={fused} "
          f"batched={batched} (W={W} words, K=8, B=16)")
    if not policy.resolve()[1]:
        check(fused and batched, "a Pallas kernel is missing from the HLO")


def evolve_phase(uni, ev, gm, seed: int):
    import numpy as np
    from repro.data.generators import dense_intervals
    times = dense_intervals(int(ev.time[-1]), 1, 32, seed=seed)[0]
    print(f"  churn: interval {times[0]}..{times[-1]} (32 points)")
    for op in ("components", "pagerank"):
        kw = {"tol": 1e-6} if op == "pagerank" else {}
        t0 = time.perf_counter()
        inc = gm.evolve(times, op, **kw)
        t1 = time.perf_counter()
        rec = gm.evolve(times, op, incremental=False, **kw)
        t2 = time.perf_counter()
        check(inc.times == rec.times, f"{op}: timepoints differ")
        if op == "components":
            for t, a, b in zip(inc.times, inc.values, rec.values):
                check(np.array_equal(a, b), f"components t={t} differ")
            err = 0.0
        else:
            err = max(float(np.abs(a - b).sum())
                      for a, b in zip(inc.values, rec.values))
            check(err <= PAGERANK_L1,
                  f"pagerank L1 {err} > {PAGERANK_L1}")
        print(f"  {op}: incremental {t1 - t0:.2f}s "
              f"(iters {sum(inc.stats['solver_iters'])}), recompute "
              f"{t2 - t1:.2f}s (iters {sum(rec.stats['solver_iters'])}), "
              f"max L1 diff {err!r}")


def train_phase(uni, ev, gm, seed: int):
    import jax
    import numpy as np
    from repro.core import SnapshotBatchLoader
    from temporal_gnn_train import (D_IN, init_params, snapshot_stream,
                                    train_step)

    tmax = int(ev.time[-1])
    grid = [int(t) for t in np.linspace(tmax // 4, int(tmax * 0.8), 16)]
    params, opt_state = init_params(jax.random.PRNGKey(seed))
    loader = SnapshotBatchLoader(gm, grid, batch_size=4,
                                 label_horizon=tmax // 10, d_in=D_IN,
                                 seed=seed)
    first = next(iter(loader))
    oracle = Oracle(uni, ev)
    for j, t in enumerate(first["times"]):
        truth = oracle(t)
        check(np.array_equal(np.asarray(first["label_mask"][j]) > 0,
                             truth.node_mask), f"loader t={t}: node mask")
        check(int(first["num_edges"][j]) == int(truth.edge_mask.sum()),
              f"loader t={t}: edge count")
    stream = snapshot_stream(loader)
    losses = []
    t0 = time.perf_counter()
    for _ in range(10):
        params, opt_state, loss = train_step(params, opt_state,
                                             next(stream))
        losses.append(float(loss))
    check(all(np.isfinite(losses)), f"non-finite loss: {losses}")
    print(f"  first loader window == replay; 10 GCN steps in "
          f"{time.perf_counter() - t0:.2f}s, loss {losses[0]:.4f} -> "
          f"{losses[-1]:.4f}")


def serve_phase(uni, ev, gm, rng):
    import zlib
    import numpy as np
    from repro.api import Q
    from repro.core import TimeExpression
    oracle = Oracle(uni, ev)
    ts = pick_times(rng, ev, 8)
    docs = ([Q.at(t).build() for t in ts[:4]]
            + [Q.at(ts[4:6]).build(), Q.at(ts[5:8]).build()]
            + [Q.expr("t0 & ~t1", ts[6:8]).build(),
               Q.expr("t0 | t1", ts[1:3]).build()])
    t0 = time.perf_counter()
    results = gm.query.run_batch(docs)
    wall = time.perf_counter() - t0

    def crc(mask):
        return zlib.crc32(np.ascontiguousarray(np.packbits(mask)).tobytes())

    def same(payload, nm, em, what):
        check(payload["nodes"] == int(nm.sum())
              and payload["edges"] == int(em.sum())
              and payload["node_crc"] == crc(nm)
              and payload["edge_crc"] == crc(em),
              f"serve {what}: envelope != replay")

    for doc, res in zip(docs, results):
        env = res.to_dict()
        check(env["ok"], f"serve {doc.kind}: {env}")
        out = env["result"]
        if doc.kind == "snapshot":
            truth = oracle(doc.t)
            same(out, truth.node_mask, truth.edge_mask, f"t={doc.t}")
        elif doc.kind == "multipoint":
            for p in out["points"]:
                truth = oracle(p["t"])
                same(p, truth.node_mask, truth.edge_mask, f"t={p['t']}")
        else:
            tex = TimeExpression.parse(doc.expr, doc.times)
            sts = [oracle(t) for t in doc.times]
            same(out, tex.evaluate([s.node_mask for s in sts]),
                 tex.evaluate([s.edge_mask for s in sts]), doc.expr)
    print(f"  8 documents ({', '.join(d.kind for d in docs)}) in "
          f"{wall:.2f}s; every envelope ok and == replay")


def sharded_phase(uni, ev, gm, rng):
    import numpy as np
    from repro.launch.mesh import retrieval_mesh
    from repro.runtime.jax_exec import (COLLECTIVES, execute_singlepoint_jax,
                                        land_singlepoint_sharded,
                                        lowered_retrieval_hlo, sharded_masks)
    mesh = retrieval_mesh(4)
    oracle = Oracle(uni, ev)
    for t in pick_times(rng, ev, 4):
        t0 = time.perf_counter()
        out_n, out_e = land_singlepoint_sharded(gm.dg, t, mesh, pool=gm.pool)
        nm, em = sharded_masks(gm.dg, out_n, out_e)
        wall = time.perf_counter() - t0
        rows = sorted((s.index[0].start, s.device.id)
                      for s in out_e.addressable_shards)
        spans = sorted(d.id for d in out_e.sharding.device_set)
        check(len(spans) == 4, f"t={t}: output spans devices {spans}")
        one_n, one_e = execute_singlepoint_jax(gm.dg, t, pool=gm.pool)
        truth = oracle(t)
        check(np.array_equal(nm, one_n) and np.array_equal(em, one_e),
              f"t={t}: sharded != one-chip execute_singlepoint_jax")
        check(np.array_equal(nm, truth.node_mask)
              and np.array_equal(em, truth.edge_mask),
              f"t={t}: sharded != replay")
        print(f"  t={t}: edge planes {tuple(out_e.shape)} span devices "
              f"{spans}, (row, device) {rows}; == one-chip == replay "
              f"({int(em.sum())} edges, {wall:.2f}s)")
    Wp = out_e.shape[1]
    hlo = lowered_retrieval_hlo(mesh, K=8, Wp=Wp)
    found = [c for c in COLLECTIVES if c in hlo]
    check(not found, f"collectives in the sharded program: {found}")
    print(f"  collectives in the sharded HLO (K=8, Wp={Wp}): none")


# ---------------------------------------------------------------------------


def run(args, phases: Phases, stack: contextlib.ExitStack):
    import numpy as np
    size = int(args.rehearse)
    with phases.phase("device"):
        d0, count = device_phase(args.rehearse, args.chips)
    if args.chips == 4:
        with phases.phase("build"):
            uni, ev, gm = build_history("growing", size, args.seed,
                                        num_partitions=4,
                                        partition_fn="word_cyclic")
            stack.callback(gm.close)
        with phases.phase("sharded"):
            sharded_phase(uni, ev, gm, np.random.default_rng(args.seed))
        return d0, count
    with phases.phase("build"):
        grow = build_history("growing", size, args.seed)
        stack.callback(grow[2].close)
        churn = build_history("churn", size, args.seed + 1)
        stack.callback(churn[2].close)
    with phases.phase("retrieve"):
        retrieve_phase("growing", *grow, np.random.default_rng(args.seed))
        retrieve_phase("churn", *churn, np.random.default_rng(args.seed + 1))
    with phases.phase("evolve"):
        evolve_phase(*churn, args.seed)
    with phases.phase("train"):
        train_phase(*churn, args.seed)
    with phases.phase("serve"):
        serve_phase(*churn, np.random.default_rng(args.seed + 2))
    return d0, count


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the sharded retrieval path, on 4 chips")
    ap.add_argument("--rehearse", action="store_true",
                    help="small histories on the CPU, Pallas kernels in "
                         "interpret mode")
    args = ap.parse_args()
    if args.rehearse:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        os.environ["REPRO_KERNEL"] = "pallas"
        os.environ["REPRO_KERNEL_INTERPRET"] = "1"
    sys.path[:0] = [str(ROOT / "src"), str(ROOT / "examples")]
    from repro.runtime.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    phases = Phases()
    t0 = time.perf_counter()
    with contextlib.ExitStack() as stack:
        try:
            d0, count = run(args, phases, stack)
        except Exception:
            traceback.print_exc()
            return 1
    stats = d0.memory_stats() or {}
    print(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use', 'not reported')}"
          f"  total wall {time.perf_counter() - t0:.2f}s  compile "
          f"{phases.compile_s:.2f}s  cache hits {phases.cache['hits']} "
          f"misses {phases.cache['misses']}")
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind, "count": count}}),
        flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
