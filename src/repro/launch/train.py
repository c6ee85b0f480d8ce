"""Training launcher: ``--arch <id> --shape <shape>`` runs a real train
loop (reduced config on CPU; full config on a real TPU mesh), with
checkpoint/resume, LR schedule, gradient compression, and deterministic
data cursors — the fault-tolerant path a cluster job would use.

Dry-run lowering of full configs lives in ``dryrun.py``; this driver
executes real steps.
"""
from __future__ import annotations

import argparse
import os
import time

import numpy as np

import jax
import jax.numpy as jnp

from ..configs.registry import family_of, get_arch, reduced_config
from ..models import common as mc
from ..runtime.compile_cache import enable_compile_cache
from ..storage.checkpoint import restore_checkpoint, save_checkpoint
from ..storage.kv import LogFileKV
from ..training.optim import OPTIMIZERS, warmup_cosine
from ..training.trainer import make_train_step


def synth_batch(arch: str, cfg, rng: np.random.Generator, batch: int,
                seq: int):
    fam = family_of(arch)
    if fam == "lm":
        return {"tokens": jnp.asarray(
            rng.integers(0, cfg.vocab, (batch, seq)), jnp.int32)}
    if fam == "recsys":
        S = cfg.seq_len
        return {"hist_goods": jnp.asarray(rng.integers(0, cfg.n_goods, (batch, S)), jnp.int32),
                "hist_cates": jnp.asarray(rng.integers(0, cfg.n_cates, (batch, S)), jnp.int32),
                "hist_mask": jnp.asarray(rng.random((batch, S)) < 0.8),
                "target_goods": jnp.asarray(rng.integers(0, cfg.n_goods, batch), jnp.int32),
                "target_cates": jnp.asarray(rng.integers(0, cfg.n_cates, batch), jnp.int32),
                "labels": jnp.asarray(rng.integers(0, 2, batch), jnp.int32)}
    # gnn: random graph batch
    N, E = 256, 1024
    src = rng.integers(0, N, E // 2).astype(np.int32)
    dst = rng.integers(0, N, E // 2).astype(np.int32)
    ei = np.stack([np.concatenate([src, dst]), np.concatenate([dst, src])])
    b = {"edge_index": jnp.asarray(ei)}
    if cfg.kind in ("gcn", "gin"):
        b.update(x=jnp.asarray(rng.standard_normal((N, cfg.d_in)), jnp.float32),
                 labels=jnp.asarray(rng.integers(0, cfg.n_classes, N), jnp.int32),
                 label_mask=jnp.ones(N, jnp.float32))
    elif cfg.kind == "meshgraphnet":
        b.update(x=jnp.asarray(rng.standard_normal((N, cfg.d_node_in)), jnp.float32),
                 edge_attr=jnp.asarray(rng.standard_normal((E, cfg.d_edge_in)), jnp.float32),
                 target=jnp.asarray(rng.standard_normal((N, cfg.d_out)), jnp.float32))
    else:
        T = 4 * E
        b.update(z=jnp.asarray(rng.integers(1, 10, N), jnp.int32),
                 pos=jnp.asarray(rng.standard_normal((N, 3)), jnp.float32),
                 triplet_kj=jnp.asarray(rng.integers(0, E, T), jnp.int32),
                 triplet_ji=jnp.asarray(rng.integers(0, E, T), jnp.int32),
                 graph_ids=jnp.zeros(N, jnp.int32),
                 target=jnp.asarray(rng.standard_normal((1, cfg.d_out)), jnp.float32))
    return b


def make_loss(arch: str, cfg):
    fam = family_of(arch)
    if fam == "lm":
        from ..models.transformer import model as tm
        return lambda p, b: tm.loss_fn(p, b, cfg), tm.param_defs(cfg)
    if fam == "recsys":
        from ..models.recsys.din import din_loss, din_param_defs
        return lambda p, b: din_loss(p, b, cfg), din_param_defs(cfg)
    from ..models.gnn import gnn_loss, gnn_param_defs
    return lambda p, b: gnn_loss(p, b, cfg), gnn_param_defs(cfg)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--full-config", action="store_true",
                    help="use the full (production) config — TPU cluster only")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--grad-compression", default=None,
                    choices=[None, "bf16", "int8"])
    args = ap.parse_args()
    enable_compile_cache()

    if args.full_config:
        cfg, opt_name = get_arch(args.arch)
    else:
        cfg = reduced_config(args.arch)
        _, opt_name = get_arch(args.arch)
    print(f"arch={args.arch} family={family_of(args.arch)} opt={opt_name}")

    loss_fn, defs = make_loss(args.arch, cfg)
    params = mc.init_params(defs, jax.random.PRNGKey(0))
    n_params = sum(np.prod(x.shape) for x in jax.tree.leaves(params))
    print(f"params: {n_params/1e6:.2f}M")

    opt = OPTIMIZERS[opt_name](lr=args.lr,
                               schedule=warmup_cosine(args.lr, 20, args.steps))
    opt_state = opt[0](params)
    step_fn = jax.jit(make_train_step(loss_fn, opt,
                                      grad_compression=args.grad_compression))

    store = None
    start = 0
    if args.ckpt_dir:
        store = LogFileKV(args.ckpt_dir)
        try:
            (params, opt_state), extra, start = restore_checkpoint(
                store, like=(params, opt_state))
            print(f"resumed @ step {start}")
        except (FileNotFoundError, KeyError):
            pass

    rng = np.random.default_rng(0)
    t0 = time.time()
    m = {}
    for step in range(start, args.steps):
        batch = synth_batch(args.arch, cfg, rng, args.batch, args.seq)
        params, opt_state, m = step_fn(params, opt_state, batch)
        if (step + 1) % 20 == 0:
            dt = (time.time() - t0) / (step - start + 1)
            print(f"step {step+1:5d}  loss {float(m['loss']):.4f}  "
                  f"{dt*1000:.0f} ms/step")
        if store and (step + 1) % args.ckpt_every == 0:
            save_checkpoint(store, step + 1, (params, opt_state),
                            extra={"data_cursor": step + 1})
    print(f"final loss {float(m['loss']):.4f}")


if __name__ == "__main__":
    main()
