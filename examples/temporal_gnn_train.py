"""End-to-end driver: train a GCN over *historical snapshots* served by the
DeltaGraph — the paper's substrate feeding an ML training loop, with
checkpoint/resume fault tolerance.

The task: node classification where the label is whether a node's degree
will grow in the future (a simple self-supervised temporal target), trained
across a stream of snapshot windows served by
:class:`repro.core.SnapshotBatchLoader` — interval retrieval runs on the
batched device path (double-buffered prefix-chain sweep) and the degree
features come from the fused delta-apply analytics kernel, so the training
loop never replays events or scatters degrees on the host.

The model is written here, in plain JAX: a two-layer GCN (Kipf & Welling)
trained with Adam, one step per snapshot.

Run:  PYTHONPATH=src python examples/temporal_gnn_train.py [--steps 300]
"""
import argparse
import os
import tempfile
import time
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import GraphManager, SnapshotBatchLoader
from repro.data.generators import churn_network
from repro.runtime.compile_cache import enable_compile_cache
from repro.storage.checkpoint import restore_checkpoint, save_checkpoint
from repro.storage.kv import LogFileKV

D_IN, HIDDEN, CLASSES = 16, 32, 2
LR = 5e-3
BETA1, BETA2, EPS = 0.9, 0.999, 1e-8


@partial(jax.jit, static_argnames=("d_in", "hidden", "classes"))
def init_params(key, d_in: int = D_IN, hidden: int = HIDDEN,
                classes: int = CLASSES):
    """The GCN's two weight matrices (normal, scaled by fan-in) and zero
    biases, and Adam's zero state ``(m, v, step)``."""
    dims = [d_in, hidden, classes]
    params = {}
    for i, k in enumerate(jax.random.split(key, 2)):
        params[f"w{i}"] = (jax.random.normal(k, (dims[i], dims[i + 1]))
                           / jnp.sqrt(dims[i]))
        params[f"b{i}"] = jnp.zeros((dims[i + 1],))
    zeros = jax.tree.map(jnp.zeros_like, params)
    return params, (zeros, zeros, jnp.zeros((), jnp.float32))


def _forward(params, x, edge_index, edge_mask):
    """Symmetric-normalised propagation over the live edges (``edge_mask``
    weights each directed entry of ``edge_index``), with self-loops."""
    src, dst = edge_index[0], edge_index[1]
    n = x.shape[0]
    deg = jax.ops.segment_sum(edge_mask, dst, num_segments=n) + 1.0
    norm = jax.lax.rsqrt(deg)
    for i in range(2):
        h = x @ params[f"w{i}"]
        m = h[src] * (norm[src] * edge_mask)[:, None]
        x = (jax.ops.segment_sum(m, dst, num_segments=n) * norm[:, None]
             + h * norm[:, None] ** 2 + params[f"b{i}"])
        if i == 0:
            x = jax.nn.relu(x)
    return x


def _loss(params, batch):
    logits = _forward(params, batch["x"], batch["edge_index"],
                      batch["edge_mask"])
    nll = -jnp.take_along_axis(jax.nn.log_softmax(logits),
                               batch["labels"][:, None], axis=1)[:, 0]
    lmask = batch["label_mask"]
    return (nll * lmask).sum() / jnp.maximum(lmask.sum(), 1.0)


@jax.jit
def train_step(params, opt_state, batch):
    """One Adam step on one snapshot's batch; returns ``(params,
    opt_state, loss)``, the loss before the step."""
    loss, g = jax.value_and_grad(_loss)(params, batch)
    m, v, t = opt_state
    t = t + 1
    m = jax.tree.map(lambda a, b: BETA1 * a + (1 - BETA1) * b, m, g)
    v = jax.tree.map(lambda a, b: BETA2 * a + (1 - BETA2) * b * b, v, g)
    c1, c2 = 1 - BETA1 ** t, 1 - BETA2 ** t
    params = jax.tree.map(lambda w, a, b: w - LR * (a / c1)
                          / (jnp.sqrt(b / c2) + EPS), params, m, v)
    return params, (m, v, t), loss


def snapshot_stream(loader: SnapshotBatchLoader):
    """Endless per-snapshot training examples from windowed loader batches.

    The loader yields ``[T, ...]`` window stacks (one batched retrieval +
    one fused analytics pass per window); each timepoint slice is a
    static-shape batch for the jit'd train step."""
    while True:
        for b in loader:
            for j in range(len(b["times"])):
                yield {"x": b["x"][j], "edge_index": b["edge_index"],
                       "edge_mask": b["edge_mask"][j],
                       "labels": b["labels"][j],
                       "label_mask": b["label_mask"][j]}


def main(argv=None) -> list[float]:
    """Train, resuming from the latest checkpoint in ``--ckpt-dir``;
    returns the losses of the steps this call ran."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--ckpt-dir", default=None)
    args = ap.parse_args(argv)
    enable_compile_cache()

    print("building historical trace + DeltaGraph index ...")
    uni, ev = churn_network(n_initial_edges=600, n_events=6000, seed=5)
    gm = GraphManager(uni, ev, L=400, k=4, diff_fn="balanced")
    tmax = int(ev.time[-1])

    params, opt_state = init_params(jax.random.PRNGKey(0))

    ckpt_dir = args.ckpt_dir or os.path.join(tempfile.gettempdir(),
                                             "repro_gnn_ckpt")
    store = LogFileKV(ckpt_dir)
    start = 0
    try:
        (params, opt_state), _, start = restore_checkpoint(
            store, like=(params, opt_state))
        print(f"resumed from step {start}")
    except (FileNotFoundError, KeyError):
        pass

    lo, hi = tmax // 4, int(tmax * 0.8)
    grid = sorted({int(t) for t in
                   np.linspace(lo, hi, 64)})
    loader = SnapshotBatchLoader(gm, grid, batch_size=4,
                                 label_horizon=tmax // 10, d_in=D_IN)
    stream = snapshot_stream(loader)
    losses = []
    t0 = time.time()
    for step in range(start, args.steps):
        params, opt_state, loss = train_step(params, opt_state, next(stream))
        losses.append(float(loss))
        if (step + 1) % 50 == 0:
            print(f"step {step+1:4d} loss {losses[-1]:.4f} "
                  f"({(time.time()-t0)/(step-start+1)*1000:.0f} ms/step)")
        if (step + 1) % args.ckpt_every == 0:
            save_checkpoint(store, step + 1, (params, opt_state))
            print(f"  checkpointed @ {step+1}")
    save_checkpoint(store, args.steps, (params, opt_state))
    store.close()
    gm.close()
    print("done — final loss", losses[-1] if losses else None)
    return losses


if __name__ == "__main__":
    main()
