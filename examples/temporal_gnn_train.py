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

Run:  PYTHONPATH=src python examples/temporal_gnn_train.py [--steps 300]
"""
import argparse
import os
import tempfile
import time

import numpy as np

import jax

from repro.core import GraphManager, SnapshotBatchLoader
from repro.data.generators import churn_network
from repro.models import common as mc
from repro.models.gnn import GCNConfig, gnn_loss, gnn_param_defs
from repro.runtime.compile_cache import enable_compile_cache
from repro.storage.checkpoint import restore_checkpoint, save_checkpoint
from repro.storage.kv import LogFileKV
from repro.training.optim import OPTIMIZERS
from repro.training.trainer import make_train_step


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


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--ckpt-dir", default=None)
    args = ap.parse_args()
    enable_compile_cache()

    print("building historical trace + DeltaGraph index ...")
    uni, ev = churn_network(n_initial_edges=600, n_events=6000, seed=5)
    gm = GraphManager(uni, ev, L=400, k=4, diff_fn="balanced")
    tmax = int(ev.time[-1])

    cfg = GCNConfig(d_in=16, d_hidden=32, n_layers=2, n_classes=2)
    params = mc.init_params(gnn_param_defs(cfg), jax.random.PRNGKey(0))
    opt = OPTIMIZERS["adamw"](lr=5e-3)
    opt_state = opt[0](params)
    step_fn = jax.jit(make_train_step(lambda p, b: gnn_loss(p, b, cfg), opt))

    ckpt_dir = args.ckpt_dir or os.path.join(tempfile.gettempdir(),
                                             "repro_gnn_ckpt")
    store = LogFileKV(ckpt_dir)
    start = 0
    try:
        (params, opt_state), extra, start = restore_checkpoint(
            store, like=(params, opt_state))
        print(f"resumed from step {start}")
    except (FileNotFoundError, KeyError):
        pass

    lo, hi = tmax // 4, int(tmax * 0.8)
    grid = sorted({int(t) for t in
                   np.linspace(lo, hi, 64)})
    loader = SnapshotBatchLoader(gm, grid, batch_size=4,
                                 label_horizon=tmax // 10, d_in=cfg.d_in)
    stream = snapshot_stream(loader)
    t0 = time.time()
    for step in range(start, args.steps):
        batch = next(stream)
        params, opt_state, m = step_fn(params, opt_state, batch)
        if (step + 1) % 50 == 0:
            print(f"step {step+1:4d} loss {float(m['loss']):.4f} "
                  f"({(time.time()-t0)/(step-start+1)*1000:.0f} ms/step)")
        if (step + 1) % args.ckpt_every == 0:
            save_checkpoint(store, step + 1, (params, opt_state))
            print(f"  checkpointed @ {step+1}")
    save_checkpoint(store, args.steps, (params, opt_state))
    print("done — final loss", float(m["loss"]))


if __name__ == "__main__":
    main()
