"""Distributed semantics, run in subprocesses with 8 virtual devices
(XLA_FLAGS must be set before jax init, so each case is its own process).

* sharded retrieval == oracle, and its HLO contains **zero collectives**
  (the paper's 'no network communication during retrieval');
* elastic replan keeps all partitions served after a worker死;
* straggler mitigation finishes with bounded duplicate work.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

pytestmark = pytest.mark.slow  # subprocess re-inits JAX; multi-second tests

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_sub(code: str) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_sharded_retrieval_correct_and_collective_free():
    out = run_sub("""
        import numpy as np, jax
        from repro.core import GraphManager, replay
        from repro.data.generators import churn_network
        from repro.runtime.jax_exec import (execute_singlepoint_sharded,
                                            lowered_retrieval_hlo)
        uni, ev = churn_network(n_initial_edges=150, n_events=900, seed=43)
        gm = GraphManager(uni, ev, L=80, k=2, num_partitions=8,
                          partition_fn="word_cyclic")
        mesh = jax.make_mesh((8,), ("data",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        rng = np.random.default_rng(2)
        for t in rng.integers(0, int(ev.time[-1]) + 3, 5):
            t = int(t)
            truth = replay(uni, ev, t)
            nm, em = execute_singlepoint_sharded(gm.dg, t, mesh, pool=gm.pool)
            assert np.array_equal(nm, truth.node_mask), t
            assert np.array_equal(em, truth.edge_mask), t
        hlo = lowered_retrieval_hlo(mesh, K=5, Wp=64)
        bad = [w for w in ("all-gather", "all-reduce", "reduce-scatter",
                           "all-to-all", "collective-permute") if w in hlo]
        assert not bad, bad
        print("OK")
    """)
    assert "OK" in out


def test_elastic_replan():
    from repro.runtime.fault import elastic_replan
    before = elastic_replan(16, ["w0", "w1", "w2", "w3"])
    after = elastic_replan(16, ["w0", "w1", "w3"])  # w2 died
    assert set(after.values()) <= {"w0", "w1", "w3"}
    assert set(after.keys()) == set(range(16))
    moved = sum(1 for p in before
                if before[p] != after[p] and before[p] != "w2")
    # consistent hashing: partitions not owned by the dead worker rarely move
    assert moved <= 4


def test_heartbeat_and_straggler():
    from repro.runtime.fault import (FetchTask, HeartbeatTracker,
                                     StragglerMitigator)
    clock = [0.0]
    hb = HeartbeatTracker(["a", "b"], timeout=5, clock=lambda: clock[0])
    clock[0] = 3.0
    hb.beat("a")
    clock[0] = 7.0
    assert hb.dead() == ["b"] and hb.alive() == ["a"]

    tasks = [FetchTask(p, f"k{p}_{i}", size_est=100 * (p + 1))
             for p in range(4) for i in range(5)]
    sm = StragglerMitigator(tasks, hedge_frac=0.2)
    assigned = []
    while True:
        t = sm.assign()
        if t is None:
            break
        assigned.append(t.key)
        sm.complete(t.key)
        if sm.finished():
            break
    assert sm.finished()
    assert len(set(assigned)) == len(tasks)
    # first assignment comes from the largest-deficit partition (p=3)
    assert assigned[0].startswith("k3_")

