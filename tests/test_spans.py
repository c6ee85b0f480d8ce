"""Spans of the retrieval path (`runtime/spans.py`) and the copy helpers
that carry their bytes (`runtime/staging.py`).

One `SnapshotBatchLoader` batch runs under a profiler trace on the CPU;
`loader.batch` must close before the batch reaches its consumer, and the
copy spans must carry exactly the bytes the batch's copies move.  Two
Pallas batches on a fresh universe run under another trace, and served
evolve documents under a third; together they must hold every program
span, `kernel.bucket` and the served path's among them, and a served
envelope's times must be its spans' times.  A source scan pins `NAMES` to
the span literals in `src/`, so a renamed span cannot drop out of a trace
reader that selects by `NAMES`.
"""
import glob
import re
from collections import defaultdict
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import GraphManager, SnapshotBatchLoader
from repro.data.generators import churn_network
from repro.runtime import spans, staging

SRC = Path(__file__).resolve().parents[1] / "src"
# reading an event's stats warns that its type names no module
pytestmark = pytest.mark.filterwarnings(
    "ignore:builtin type event_stats:DeprecationWarning")


def _host_events(trace_dir: Path) -> dict[str, list]:
    """``name -> [(line index, start_ns, end_ns, stats)]`` over the host
    plane of the one trace written under ``trace_dir``."""
    from jax.profiler import ProfileData
    (path,) = glob.glob(str(trace_dir / "plugins/profile/*/*.xplane.pb"))
    out = defaultdict(list)
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                out[e.name].append((i, e.start_ns, e.end_ns, dict(e.stats)))
    return out


def _loader(impl=None):
    """A loader over a small churn history: one batch of 4 times and
    their label horizons."""
    uni, ev = churn_network(n_initial_edges=150, n_events=1200, seed=5)
    gm = GraphManager(uni, ev, L=64, k=2, cache_bytes=0)
    tmax = int(ev.time[-1])
    times = list(range(tmax // 8, tmax // 2, tmax // 10))[:4]
    return gm, SnapshotBatchLoader(gm, times, batch_size=4,
                                   label_horizon=tmax // 20, d_in=8,
                                   impl=impl)


@pytest.fixture(scope="module")
def traced_batch(tmp_path_factory):
    """One loader batch under a trace."""
    gm, loader = _loader()
    warm = next(iter(loader))                 # compiles outside the trace
    jax.block_until_ready(warm["x"])
    trace_dir = tmp_path_factory.mktemp("trace")
    with jax.profiler.trace(str(trace_dir)):
        it = iter(loader)
        batch = next(it)
        with jax.profiler.TraceAnnotation("test.consumer"):
            jax.block_until_ready(batch["x"])
        it.close()
    gm.close()
    return {"events": _host_events(trace_dir), "batch": batch,
            "E": gm.universe.num_edges}


@pytest.fixture(scope="module")
def traced_pallas_batches(tmp_path_factory):
    """Two Pallas batches from two loaders under a trace, on a universe
    no loader has seen, so its bucket tables are built inside the trace."""
    gm, loader = _loader("pallas")
    warm = next(iter(loader))                 # compiles outside the trace
    jax.block_until_ready(warm["x"])
    gm.close()
    gm, loader = _loader("pallas")            # same shapes, new universe
    trace_dir = tmp_path_factory.mktemp("trace_pallas")
    with jax.profiler.trace(str(trace_dir)):
        for _ in range(2):
            jax.block_until_ready(next(iter(loader))["x"])
            loader = SnapshotBatchLoader(gm, loader.times, batch_size=4,
                                         label_horizon=loader.label_horizon,
                                         d_in=8, impl="pallas")
    gm.close()
    return {"events": _host_events(trace_dir), "universe": gm.universe}


@pytest.fixture(scope="module")
def traced_session(tmp_path_factory):
    """Two evolve documents and a multipoint one through a socket
    session under a trace, each compiled outside it first."""
    import json
    import socket

    from repro.launch.server import QueryServer
    gm, loader = _loader()
    t = loader.times
    docs = [{"kind": "evolve", "op": op, "times": t, "id": op}
            for op in ("pagerank", "components")]
    docs.append({"kind": "multipoint", "times": t[:2], "id": "mp"})
    trace_dir = tmp_path_factory.mktemp("trace_served")
    with QueryServer(gm, workers=1) as srv:
        with socket.create_connection((srv.host, srv.port), timeout=60) as c:
            f = c.makefile("rw", encoding="utf-8", newline="\n")

            def rpc(doc):
                f.write(json.dumps(doc) + "\n")
                f.flush()
                return json.loads(f.readline())

            for doc in docs:
                rpc(dict(doc, id="warm"))
            with jax.profiler.trace(str(trace_dir)):
                envs = [rpc(doc) for doc in docs]
            f.close()
    gm.close()
    return {"events": _host_events(trace_dir), "envelopes": envs}


def test_every_span_is_in_the_trace(traced_pallas_batches, traced_session):
    ev = dict(traced_pallas_batches["events"])
    ev.update((k, v) for k, v in traced_session["events"].items() if v)
    missing = [n for n in spans.NAMES if not ev.get(n)]
    assert not missing


def test_envelope_times_are_their_spans_times(traced_session):
    """Each envelope's solve, advance and reply seconds are what its
    spans cover in the trace (one document at a time, one worker), and
    each solve span carries its operator, iterations and edge rows."""
    ev, envs = traced_session["events"], traced_session["envelopes"]
    dispatch = sorted(ev["sched.dispatch"], key=lambda e: e[1])
    replies = sorted(ev["reply.render"], key=lambda e: e[1])
    assert len(dispatch) == len(replies) == len(envs)
    for env, (line, a, b, st), reply in zip(envs, dispatch, replies):
        assert st["docs"] == 1
        for name, key in (("solve.fixpoint", "solve_s"),
                          ("evolve.advance", "advance_s")):
            inside = [e for e in ev[name] if e[0] == line and a <= e[1]
                      and e[2] <= b]
            secs = sum(e[2] - e[1] for e in inside) / 1e9
            assert env["stats"][key] == pytest.approx(secs, abs=2e-4)
        assert env["stats"]["reply_s"] <= (reply[2] - reply[1]) / 1e9
        if env["kind"] == "evolve":
            engine = env["result"]["engine_stats"]
            solves = sorted((e for e in ev["solve.fixpoint"]
                             if e[0] == line and a <= e[1] and e[2] <= b),
                            key=lambda e: e[1])
            assert [e[3]["op"] for e in solves] == [env["id"]] * len(solves)
            assert [e[3]["iters"] for e in solves] == engine["solver_iters"]
            assert [e[3]["edges"] for e in solves] == engine["solver_edges"]


def test_bucket_tables_build_once_outside_dispatch(traced_pallas_batches):
    """One `kernel.bucket` a side, in the first batch only, never inside
    `kernel.dispatch`; it holds the tables' four uploads."""
    from repro.kernels import bucket_edges
    ev = traced_pallas_batches["events"]
    first, _ = sorted(ev["loader.batch"], key=lambda e: e[1])
    builds = ev["kernel.bucket"]
    assert len(builds) == 2
    for line, a, b, _ in builds:
        assert line == first[0] and first[1] <= a and b <= first[2]
        for dline, da, db, _ in ev["kernel.dispatch"]:
            assert dline != line or db <= a or b <= da
    inside = [s["bytes"] for line, a, b, s in ev["h2d.put"]
              if any(line == bl and ba <= a and b <= bb
                     for bl, ba, bb, _ in builds)]
    uni = traced_pallas_batches["universe"]
    tables = [4 * bucket_edges(ids, uni.num_nodes, 128)[1].size
              for ids in (uni.edge_src, uni.edge_dst)]
    assert sorted(inside) == sorted(2 * tables)


def test_loader_batch_closes_before_the_yield(traced_batch):
    ev = traced_batch["events"]
    (batch_span,) = ev["loader.batch"]
    (consumer,) = ev["test.consumer"]
    assert batch_span[2] <= consumer[1]
    # the batch's own work sits inside it, on the loader's thread
    for name in ("loader.assemble", "retrieve.plan", "slice.quads"):
        for line, a, b, _ in ev[name]:
            assert line == batch_span[0]
            assert batch_span[1] <= a and b <= batch_span[2]


def test_transfer_counters_match_the_copies(traced_batch):
    """The copy spans carry the bytes of the batch's copies: each array
    of the batch dict is one upload of exactly its bytes."""
    ev, b = traced_batch["events"], traced_batch["batch"]
    put = [s["bytes"] for *_, s in ev["h2d.put"]]
    copy = [s["bytes"] for *_, s in ev["d2h.copy"]]
    assert all(n > 0 for n in put + copy) and copy
    uploads = list(put)
    for k in ("x", "edge_index", "edge_mask", "label_mask", "num_edges",
              "labels"):
        uploads.remove(b[k].nbytes)
    # and _degrees uploads the edge endpoints once per call (two calls)
    for _ in range(4):
        uploads.remove(4 * traced_batch["E"])


def test_to_device_and_to_host_count_exact_bytes(tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        x = staging.to_device(np.arange(10, dtype=np.int64))
        assert staging.to_device(x) is x      # already on the device
        y = staging.to_host(jnp.ones((3, 5), jnp.float32))
        staging.to_host(np.ones(7))           # a host array moves nothing
    assert isinstance(y, np.ndarray)
    ev = _host_events(tmp_path)
    assert [s["bytes"] for *_, s in ev["h2d.put"]] == [x.nbytes] == [
        10 * x.dtype.itemsize]
    assert [s["bytes"] for *_, s in ev["d2h.copy"]] == [60]


def test_span_literals_match_names():
    used = set()
    for path in SRC.rglob("*.py"):
        used |= set(re.findall(r'\b(?:span|timed)\(\s*"([^"]+)"',
                               path.read_text()))
    assert used <= set(spans.NAMES), used - set(spans.NAMES)
    assert set(spans.NAMES) <= used, set(spans.NAMES) - used
    assert len(set(spans.NAMES)) == len(spans.NAMES)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_loader_reads_each_array_back_once(impl, monkeypatch):
    """Every `to_host` call counts as a copy, so the loader's path reads
    each device array back at most once; `segment_sum` gets host ids, so
    no edge-id array makes a round trip on either path."""
    from repro.kernels.delta_apply import ops as delta_ops
    from repro.kernels.segment_sum import ops as segment_ops
    from repro.runtime import jax_exec

    reads, real = [], staging.to_host

    def spy(x):
        if isinstance(x, jax.Array):
            reads.append(x)                   # held, so no id is reused
        return real(x)

    for mod in (staging, jax_exec, delta_ops, segment_ops):
        monkeypatch.setattr(mod, "to_host", spy)
    gm, loader = _loader(impl)
    batch = next(iter(loader))
    gm.close()
    assert batch["labels"].shape == batch["label_mask"].shape
    assert reads
    assert len({id(x) for x in reads}) == len(reads)
    assert not any(x.shape == (gm.universe.num_edges,) for x in reads)
