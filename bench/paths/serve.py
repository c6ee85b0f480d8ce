"""The ``serve`` path: documents through the program's query server.

The path's ``Driver`` starts ``QueryServer`` (``launch/server.py``) on
the cell's manager in this process, with the configuration's
``server`` settings, so every document takes the served path: session,
``BatchingScheduler``, ``QueryService``, the compiler, then
``TemporalEngine.evolve`` or ``retrieve_points``, and back as an
envelope.  The client is
``bench/serve_client.py`` in a child process that imports no JAX: a
closed loop of ``connections`` connections, each sending its next
document once it has read the previous reply.

Documents are drawn from the seed, each distinct and none repeated: a
pool of ``pool`` documents sent in order, of kinds in the ratio of ``mix``
(``pagerank`` and ``components``: evolve documents of ``evolve_points``
times, PageRank with ``reply: "full"``; ``multipoint``:
``multipoint_points`` times), the times evenly spaced over ``span`` of the
history from a start uniform over it.  Both draws are stratified, so that
the few hundred documents a window answers hold the ratio and cover the
history as the whole pool does: each block of ``sum(mix)`` documents is a
shuffle of the ratio's kinds, and each kind's starts fall, ``STRATA`` at a
time, one into each of ``STRATA`` equal parts of the history, in shuffled
order.  Running out of the pool is an error, never a cycle.

``snapshots`` counts the points of the documents whose reply arrived in
the window; the per-document stats of those replies are summed for the
metric readers.  ``verify`` checks the first document of each kind and a
share (``verify_share``) drawn from the seed, against the float64
reference (``bench/reference_analytics.py``).
"""
from __future__ import annotations

import json
import subprocess
import sys
import time
from pathlib import Path

import numpy as np

from bench import fixpoint_costs
from bench import reference as ref
from bench import reference_analytics as ana

# the harness spans this path opens, the window's first
SPANS = ("serve.window",)

CLIENT = Path(__file__).resolve().parents[1] / "serve_client.py"
KINDS = ("pagerank", "components", "multipoint")
STATS = ("queue_s", "advance_s", "solve_s", "reply_s")
# parts of the history a kind's starts are spread over, one each in turn
STRATA = 16


def _shuffled_blocks(rng, block: np.ndarray, n: int) -> np.ndarray:
    """``n`` values: shuffles of ``block``, one after another."""
    reps = -(-n // block.size)
    return np.concatenate([rng.permutation(block)
                           for _ in range(reps)])[:n]


def documents(rng, tmax: int, traffic: dict, n: int,
              kinds=None) -> list[dict]:
    """``n`` documents drawn as the module docstring says, ``id`` their
    index; ``kinds``, indices into ``KINDS``, fixes their kinds."""
    if kinds is None:
        kinds = _shuffled_blocks(rng, np.repeat(
            np.arange(len(KINDS)), [traffic["mix"][k] for k in KINDS]), n)
    kinds = np.asarray(kinds)
    width = int(tmax * traffic["span"])
    starts = np.zeros(n, np.int64)
    for k in range(len(KINDS)):
        mine = np.nonzero(kinds == k)[0]
        strata = _shuffled_blocks(rng, np.arange(STRATA), mine.size)
        starts[mine] = ((strata + rng.random(mine.size)) / STRATA
                        * (tmax - width))
    out = []
    for i, (k, start) in enumerate(zip(kinds, starts)):
        kind = KINDS[k]
        points = traffic["multipoint_points" if kind == "multipoint"
                         else "evolve_points"]
        times = [int(t) for t in
                 np.linspace(start, start + width, points)]
        if kind == "multipoint":
            out.append({"kind": "multipoint", "times": times, "id": i})
        else:
            out.append({"kind": "evolve", "op": kind, "times": times,
                        "id": i, "reply": ("full" if kind == "pagerank"
                                           else "summary")})
    return out


def kind_of(doc: dict) -> str:
    return doc.get("op", doc["kind"])


def compare(h, pairs) -> dict:
    """Each ``(document, envelope)`` against the reference: multipoint
    points and component labels exactly (counts and CRCs as the envelope
    carries them), PageRank points by their L1 distance.  A point whose
    answer is missing, malformed or unequal counts in ``wrong_points``."""
    times = {t for d, _ in pairs for t in d["times"]}
    snaps = ref.Snapshots(h, times)
    wrong, l1_max = 0, 0.0
    for doc, env in pairs:
        res = env.get("result") or {}
        kind = kind_of(doc)
        if kind == "multipoint":
            got = {p.get("t"): p for p in res.get("points", [])}
        else:
            ok = res.get("times") == doc["times"]
            got = dict(zip(doc["times"], res.get("values", []))) if ok else {}
        for t in doc["times"]:
            g = got.get(t)
            nm, em = snaps.node_mask(t), snaps.edge_mask(t)
            if g is None:
                wrong += 1
            elif kind == "multipoint":
                wrong += (g.get("nodes"), g.get("edges"), g.get("node_crc"),
                          g.get("edge_crc")) != (
                    int(nm.sum()), int(em.sum()), ana.crc(np.packbits(nm)),
                    ana.crc(np.packbits(em)))
            elif kind == "components":
                want = ana.components(h, nm, em)
                wrong += g != {"size": want.size, "dtype": "int32",
                               "crc": ana.crc(want)}
            else:
                want = ana.pagerank(h, nm, em)
                g = np.asarray(g, np.float64)
                if g.shape != want.shape:
                    wrong += 1
                    continue
                l1_max = max(l1_max, float(np.abs(g - want).sum()))
    return {"wrong_points": wrong, "pagerank_l1_max": l1_max}


class Driver:
    """A query server in this process, its client in a child."""

    def __init__(self, cell, traffic: dict) -> None:
        self.cell, self.traffic = cell, traffic
        self.server = self.proc = None
        self.kept: dict[int, dict] = {}
        self.failures: list = []

    def build(self) -> None:
        from repro.launch.server import QueryServer
        c, tr = self.cell, self.traffic
        tmax = c.hist.tmax
        self.docs = documents(np.random.default_rng([c.seed, 4]), tmax, tr,
                              tr["pool"])
        self.warm_docs = documents(np.random.default_rng([c.seed, 5]), tmax,
                                   tr, len(KINDS), range(len(KINDS)))
        draws = np.random.default_rng([c.seed, 6]).random(len(self.docs))
        first = {next(i for i, d in enumerate(self.docs) if kind_of(d) == k)
                 for k in KINDS}
        self.keep = sorted(first | set(np.nonzero(
            draws < tr["verify_share"])[0].tolist()))
        self.server = QueryServer(c.gm, **c.config["server"]).start()

    def _rpc(self, docs: list[dict]) -> None:
        """Documents one by one through a socket of this process."""
        import socket
        timeout = self.traffic["reply_timeout_s"]
        with socket.create_connection((self.server.host, self.server.port),
                                      timeout=timeout) as conn:
            f = conn.makefile("rwb")
            for d in docs:
                f.write(json.dumps(d).encode() + b"\n")
                f.flush()
                env = json.loads(f.readline())
                if not env.get("ok"):
                    raise RuntimeError(f"warm-up document failed: {env}")

    def warm(self) -> None:
        """Every fixpoint program the universe can ask for, through the
        program's own start-up call; then one document of each kind
        through the server (not from the pool); then the client,
        connected and waiting."""
        from repro.graph.algorithms import compile_fixpoints
        uni = self.cell.gm.universe
        compile_fixpoints(uni.num_nodes, uni.num_edges)
        self._rpc(self.warm_docs)
        tr = self.traffic
        self.proc = subprocess.Popen(
            [sys.executable, str(CLIENT)], stdin=subprocess.PIPE,
            stdout=subprocess.PIPE, text=True)
        self.proc.stdin.write(json.dumps({
            "host": self.server.host, "port": self.server.port,
            "connections": tr["connections"],
            "docs": [json.dumps(d) for d in self.docs], "keep": self.keep,
            "reply_timeout_s": tr["reply_timeout_s"]}) + "\n")
        self.proc.stdin.flush()
        self._read_child(30.0)

    def _read_child(self, timeout: float) -> dict:
        """The child's next stdout line, within ``timeout`` seconds."""
        import selectors
        with selectors.DefaultSelector() as sel:
            sel.register(self.proc.stdout, selectors.EVENT_READ)
            if not sel.select(timeout):
                raise TimeoutError(f"the client said nothing in {timeout}s")
        line = self.proc.stdout.readline()
        if not line:
            raise RuntimeError(f"the client exited ({self.proc.wait()})")
        return json.loads(line)

    def drive(self, tracer) -> dict:
        c, tr = self.cell, self.traffic
        with tracer.window():
            c.t0 = time.monotonic()
            close = c.t0 + c.seconds
            self.proc.stdin.write(json.dumps({"close": close}) + "\n")
            self.proc.stdin.flush()
            try:
                code = self.proc.wait(timeout=c.seconds)
                raise RuntimeError(f"the client exited in the window ({code})")
            except subprocess.TimeoutExpired:
                pass
            c.t_end = time.monotonic()
        report = self._read_child(tr["reply_timeout_s"] + 10)
        if report["exhausted"]:
            raise RuntimeError(f"the pool of {len(self.docs)} documents ran "
                               f"out inside the window; make it larger")
        return self._window(report, close)

    def _window(self, report: dict, close: float) -> dict:
        """Sums over the documents answered in the window; failures and
        kept envelopes over every document answered."""
        N = self.cell.hist.num_nodes
        w = {"snapshots": 0, "docs": 0, "analytic_points": 0,
             "failed": 0, "sent": len(report["records"]) + report["stuck"],
             "stats": {}, "solver_iters": 0, "pagerank_solves": 0,
             "pagerank_bytes": 0}
        has_iters = False
        lat = []
        for i, sent, got, ok, err, stats, iters, edges, env in \
                report["records"]:
            doc = self.docs[i]
            if not ok:
                w["failed"] += 1
                self.failures.append([i, err])
                continue
            if env is not None:
                self.kept[i] = json.loads(env)
            if got > close:
                continue
            lat.append(got - sent)
            w["docs"] += 1
            w["snapshots"] += len(doc["times"])
            for k in STATS:
                if stats and k in stats:
                    w["stats"][k] = w["stats"].get(k, 0.0) + stats[k]
            if doc["kind"] == "evolve":
                w["analytic_points"] += len(doc["times"])
                if iters:
                    has_iters = True
                    w["solver_iters"] += sum(iters)
                if kind_of(doc) == "pagerank" and iters and edges:
                    w["pagerank_solves"] += len(iters)
                    w["pagerank_bytes"] += sum(
                        n * fixpoint_costs.pagerank_iteration_bytes(N, e)
                        for n, e in zip(iters, edges))
        w["failed"] += report["stuck"]
        if not has_iters:
            w["solver_iters"] = None
        if not w["pagerank_solves"]:
            w["pagerank_bytes"] = None
        w["latency_ms_p50"] = (1e3 * float(np.median(lat)) if lat else None)
        return w

    def close(self) -> None:
        if self.proc is not None and self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait()
        if self.server is not None:
            self.server.close()

    def verify(self, window: dict) -> dict:
        t0 = time.perf_counter()
        pairs = [(self.docs[i], env) for i, env in sorted(self.kept.items())]
        checks = compare(self.cell.hist, pairs)
        kinds = [kind_of(d) for d, _ in pairs]
        print(f"verified {sum(len(d['times']) for d, _ in pairs)} points "
              f"of {len(pairs)} documents "
              f"({', '.join(f'{kinds.count(k)} {k}' for k in KINDS)}) "
              f"in {time.perf_counter() - t0:.1f} s; "
              f"failures: {self.failures[:8]}", flush=True)
        return {**checks, "failed_docs": window["failed"]}
