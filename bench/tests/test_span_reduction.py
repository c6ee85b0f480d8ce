"""The thread-aware reduction of the program's spans (`bench/program_spans.py`)
on synthetic event lists, and one traced rehearsal run through the
harness whose span metrics and transfer bytes are checked against the
run's own shapes."""
import json

import pytest

from bench import program_spans as ps
from bench import trace

MS = 1_000_000
NAMES = ("retrieve.plan", "host.pack", "codec.decode", "kv.wait", "h2d.put",
         "loader.batch")


def ev(name, a_ms, b_ms, **stats):
    return (name, a_ms * MS, (b_ms - a_ms) * MS, stats)


def busy(*ivs_ms):
    return {"/device:TPU:0": [
        ("XLA Modules", f"jit_p{i}({i})", a * MS, (b - a) * MS)
        for i, (a, b) in enumerate(ivs_ms)]}


def test_worker_span_does_not_take_the_window_threads_gap():
    window = [ev("loader.window", 0, 100), ev("loader.next", 0, 50),
              ev("loader.batch", 0, 48), ev("kv.wait", 10, 30),
              ev("consumer.step", 50, 100)]
    worker = [ev("codec.decode", 12, 28), ev("host.pack", 11, 12)]
    r = ps.reduce_lines(busy((40, 45), (50, 100)), [worker, window], NAMES)
    idle = dict(r["idle_gaps"])
    assert idle["kv.wait"] == pytest.approx(0.020)
    assert idle["loader.batch"] == pytest.approx(0.010 + 0.010 + 0.003)
    assert idle["loader.next"] == pytest.approx(0.002)     # [48, 50)
    assert "codec.decode" not in idle and "host.pack" not in idle
    assert sum(idle.values()) == pytest.approx(0.045)  # [0, 40), [45, 50)
    # the worker's spans are busy time, not self time
    assert r["spans"]["codec.decode"] == {"self_s": 0.0,
                                          "busy_s": pytest.approx(0.016),
                                          "count": 1, "bytes": 0}


def test_self_time_of_nested_spans_and_same_name_children():
    window = [ev("loader.window", 0, 100),
              ev("retrieve.plan", 10, 40), ev("retrieve.plan", 15, 25),
              ev("kv.wait", 30, 35), ev("loader.batch", 50, 90),
              ev("h2d.put", 60, 70, bytes=4096), ev("h2d.put", 95, 105,
                                                   bytes=8)]
    r = ps.reduce_lines({}, [window], NAMES)
    s = r["spans"]
    assert s["retrieve.plan"]["self_s"] == pytest.approx(0.025)   # 15 + 10
    assert s["retrieve.plan"]["count"] == 2
    assert s["kv.wait"]["self_s"] == pytest.approx(0.005)
    assert s["loader.batch"]["self_s"] == pytest.approx(0.030)
    # clipped to the window; bytes of every copy that starts in it
    assert s["h2d.put"]["self_s"] == pytest.approx(0.015)
    assert s["h2d.put"]["bytes"] == 4096 + 8
    assert r["window_s"] == pytest.approx(0.1)


def test_busy_time_on_worker_threads():
    window = [ev("loader.window", 0, 100), ev("kv.wait", 20, 30)]
    w1 = [ev("codec.decode", 5, 40), ev("host.pack", 1, 5)]
    w2 = [ev("codec.decode", 20, 120), ev("codec.decode", -10, 2)]
    r = ps.reduce_lines({}, [w1, window, w2], NAMES)
    s = r["spans"]
    assert s["codec.decode"]["busy_s"] == pytest.approx(0.035 + 0.080
                                                        + 0.002)
    assert s["codec.decode"]["self_s"] == 0.0
    assert s["codec.decode"]["count"] == 3
    assert s["host.pack"]["busy_s"] == pytest.approx(0.004)
    assert s["kv.wait"] == {"self_s": pytest.approx(0.010), "busy_s": 0.0,
                            "count": 1, "bytes": 0}


def test_harness_numbers_unchanged_by_program_spans():
    """``bench/trace.py`` reads the same window, busy time and kernels
    with the program's spans in the trace, and the thread-aware idle
    split equals its own where only harness spans cover the gaps."""
    device = busy((10, 14), (30, 35), (95, 105))
    harness = [("loader.window", 0, 100 * MS), ("loader.next", 0, 50 * MS),
               ("consumer.step", 50 * MS, 50 * MS)]
    program = [("retrieve.plan", 200 * MS, 1 * MS),
               ("host.pack", 60 * MS, 5 * MS)]
    before = trace.reduce_events(device, harness)
    after = trace.reduce_events(device, harness + program)
    for k in ("window_s", "busy_s", "kernels"):
        assert after[k] == before[k]
    r = ps.reduce_lines(device, [[(n, s, d, {}) for n, s, d in harness]
                                 + [(n, s, d, {}) for n, s, d
                                    in program[:1]]], NAMES)
    assert r["idle_gaps"] == [[k, pytest.approx(v)]
                              for k, v in before["breakdown"]["idle_gaps"]]


def test_no_program_spans_reads_nothing():
    window = [ev("loader.window", 0, 100), ev("loader.next", 0, 50)]
    assert ps.reduce_lines(busy((1, 2)), [window], NAMES) is None
    assert ps.reduce_lines({}, [[ev("host.pack", 0, 1)]], NAMES) is None


def test_traced_rehearsal_reports_every_span_metric(capsys, monkeypatch):
    """A traced run at the cell's rehearsal size: every metric this
    reduction feeds is present and positive, and the bytes copied to the
    device per snapshot equal what the run's shapes reckon."""
    from bench import run as harness
    from repro.core import bitmaps, temporal
    from repro.runtime import jax_exec

    batches = []                       # per loader batch: reckoned bytes
    real_iter = temporal.SnapshotBatchLoader.__iter__
    real_evolve = jax_exec.evolve_intervals_jax
    real_chains = jax_exec._apply_chains_streamed

    def planes(B, K, W):               # a base plane and K add + K del
        return B * (1 + 2 * K) * W * 4 if K else 0

    def spy_iter(self):
        uni = self.gm.universe
        N, E = uni.num_nodes, uni.num_edges
        it = real_iter(self)
        while True:
            batches.append({"W": bitmaps.num_words(N)
                            + bitmaps.num_words(E), "bytes": 0})
            b = next(it, None)
            if b is None:
                batches.pop()
                return
            T, d = len(b["times"]), self.d_in
            batches[-1]["bytes"] += (
                4 * T * N * d                 # x
                + 4 * 2 * 2 * E               # edge_index
                + 4 * T * 2 * E               # edge_mask
                + 4 * T * N * 2 + 4 * T       # label_mask, labels, num_edges
                + 2 * (4 * T * bitmaps.num_words(E) + 2 * 4 * E))  # _degrees
            yield b

    def spy_evolve(dg, intervals, **kw):
        ivs = [sorted(set(iv)) for iv in intervals]
        batches[-1]["bytes"] += planes(len(ivs),
                                       max(len(iv) for iv in ivs) - 1,
                                       batches[-1]["W"])
        return real_evolve(dg, intervals, **kw)

    def spy_chains(bn, be, chains, *a, **kw):
        batches[-1]["bytes"] += planes(len(chains),
                                       max(len(c) for c in chains),
                                       batches[-1]["W"])
        return real_chains(bn, be, chains, *a, **kw)

    monkeypatch.setattr(temporal.SnapshotBatchLoader, "__iter__", spy_iter)
    monkeypatch.setattr(jax_exec, "evolve_intervals_jax", spy_evolve)
    monkeypatch.setattr(jax_exec, "_apply_chains_streamed", spy_chains)
    rc = harness.main(["--workload", "churn-1m.interval-loader", "--seed",
                       "2147483901", "--seconds", "3", "--trace", "1",
                       "--rehearse"], allow_cpu=True)
    assert rc == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["correct"] is True
    bench = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    mine = [m["name"] for m in bench["per_layer"]
            if m["name"].split("_per_snapshot")[0] in (
                "plan_ms", "kv_wait_ms", "decode_ms", "pack_ms", "h2d_ms",
                "d2h_ms", "h2d_bytes", "d2h_bytes", "assemble_ms",
                "dispatch_ms")]
    assert len(mine) == 10
    for name in mine:
        assert out["metrics"][name]["value"] > 0, name
    window = batches[1:]               # the first batch is the warm-up
    reckoned = sum(b["bytes"] for b in window) / out["attempted"] / 1e6
    assert out["metrics"]["h2d_bytes_per_snapshot.loader"]["value"] == (
        pytest.approx(reckoned, rel=1e-12))
