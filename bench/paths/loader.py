"""The ``loader`` path: ``SnapshotBatchLoader`` windows of ``points``
evenly spaced times over ``span`` of the history, drawn from the seed,
in batches of ``batch_size`` feeding a plain-JAX GCN consumer in this
process; each step waits for the consumer's loss.
"""
from __future__ import annotations

import time

import numpy as np

from bench import reference as ref
from bench.load import interval

# the harness spans this path opens, the window's first
SPANS = ("loader.window", "loader.next", "consumer.step")


class Driver:
    """``SnapshotBatchLoader`` windows feeding a GCN consumer."""

    def __init__(self, cell, traffic: dict) -> None:
        self.cell, self.traffic = cell, traffic
        self.kept: list[dict] = []

    def build(self) -> None:
        from bench import consumer
        c, tr = self.cell, self.traffic
        tmax = c.hist.tmax
        self.horizon = int(tmax * tr["label_horizon"])
        rng = np.random.default_rng([c.seed, 2])
        self.windows = [interval(rng, tmax, tr["span"], tr["points"],
                                 tail=self.horizon)
                        for _ in range(tr["windows"])]
        self.warm_window = interval(rng, tmax, tr["span"], tr["points"],
                                    tail=self.horizon)
        self.sample_rng = np.random.default_rng([c.seed, 3])
        self.consumer = consumer.GCN(tr["d_in"], tr["hidden"], tr["classes"],
                                     tr["lr"], c.seed)

    def _loader(self, times):
        from repro.core import SnapshotBatchLoader
        tr = self.traffic
        return SnapshotBatchLoader(
            self.cell.gm, times, batch_size=tr["batch_size"],
            label_horizon=self.horizon, d_in=tr["d_in"], seed=self.cell.seed)

    def warm(self) -> None:
        """One batch through loader and consumer (the fixed-shape
        programs), then every batched-chain shape a retrieval plan can
        ask of the kernel wrapper: 1-4 chains of 1-8 steps, at the node
        and the edge plane width."""
        import jax.numpy as jnp
        from repro.core import bitmaps
        from repro.kernels import delta_apply_chain_batched
        batch = next(iter(self._loader(self.warm_window)))
        for _ in self.consumer.consume(batch):
            pass
        uni = self.cell.gm.universe
        for W in {bitmaps.num_words(uni.num_nodes),
                  bitmaps.num_words(uni.num_edges)}:
            for B in range(1, 5):
                for K in range(1, 9):
                    z = jnp.zeros((B, K, W), jnp.uint32)
                    np.asarray(delta_apply_chain_batched(z[:, 0], z, z))

    def drive(self, tracer) -> dict:
        from jax.profiler import TraceAnnotation
        c = self.cell
        steps, batches, w = 0, 0, 0
        with tracer.window():
            t0 = last = time.monotonic()
            c.t0 = t0
            close = t0 + c.seconds
            while last < close:
                it = iter(self._loader(self.windows[w % len(self.windows)]))
                w += 1
                while last < close:
                    with TraceAnnotation("loader.next"):
                        batch = next(it, None)
                    if batch is None:
                        break
                    if not self.kept or self.sample_rng.random() < 0.25:
                        self.kept.append(batch)
                    batches += 1
                    with TraceAnnotation("consumer.step"):
                        for done in self.consumer.consume(batch):
                            if done <= close:
                                steps += 1
                            last = done
            c.t_end = last
        return {"snapshots": steps, "batches": batches, "windows": w}

    def close(self) -> None:
        pass

    def verify(self, window: dict) -> dict:
        """Each kept batch against the reference: node masks, live edge
        counts, degrees (the raw degree feature), edge masks and
        degree-growth labels, snapshot by snapshot."""
        h = self.cell.hist
        times = {t for b in self.kept for t in b["times"]}
        snaps = ref.Snapshots(h, times | {t + self.horizon for t in times})
        wrong = 0
        for b in self.kept:
            label_mask = np.asarray(b["label_mask"]) > 0
            edge_mask = np.asarray(b["edge_mask"])
            num_edges = np.asarray(b["num_edges"])
            deg_feat = np.asarray(b["x"])[:, :, -1]
            labels = np.asarray(b["labels"])
            E = h.num_edges
            for j, t in enumerate(b["times"]):
                nm, em = snaps.node_mask(t), snaps.edge_mask(t)
                deg = ref.degrees(h, em)
                grow = ref.degrees(h, snaps.edge_mask(t + self.horizon)) > deg
                ok = (np.array_equal(label_mask[j], nm)
                      and int(num_edges[j]) == int(em.sum())
                      and np.array_equal(deg_feat[j], deg.astype(np.float32))
                      and np.array_equal(edge_mask[j, :E] > 0, em)
                      and np.array_equal(edge_mask[j, E:] > 0, em)
                      and np.array_equal(labels[j], grow.astype(np.int32)))
                wrong += not ok
        print(f"verified {sum(len(b['times']) for b in self.kept)} "
              f"snapshots in {len(self.kept)} batches", flush=True)
        return {"wrong_snapshots": wrong}

